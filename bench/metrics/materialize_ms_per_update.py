"""Host time inside ``Population.materialize`` (the streaming data
plane) per committed update."""


def read(run):
    if "materialize" not in run.spans or not run.updates:
        return None
    return run.spans["materialize"] / run.updates * 1e3
