"""Device time of the fused round step's program per committed update."""
import re

#: the fused FedAT round step is ``jax.jit(step)`` in core/executor.py
STEP_MODULE = re.compile(r"^jit_step\b")


def read(run):
    t = sum(v for k, v in run.trace["modules"].items()
            if STEP_MODULE.match(k))
    if not t or not run.updates:
        return None
    return t / run.updates * 1e3
