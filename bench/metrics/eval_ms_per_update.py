"""Host time inside the evaluation calls (``SimEnv.evaluate`` and the
strategy's ``on_eval``) per committed update."""


def read(run):
    if "eval" not in run.spans or not run.updates:
        return None
    return run.spans["eval"] / run.updates * 1e3
