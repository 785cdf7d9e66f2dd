"""Share of the traced window in which no operation ran on the device
(mean over the chips used), in percent."""


def read(run):
    window = run.trace["window_s"]
    if window <= 0:
        return None
    return (1.0 - run.trace["busy_s"] / window) * 100
