"""Training operations of the live rows over the window, against the
chips' bf16 peak, in percent (masked padding rows are not counted)."""


def read(run):
    if not run.peak_flops or not run.train_flops:
        return None
    window = run.trace["window_s"]
    return run.train_flops / (window * run.chips * run.peak_flops) * 100
