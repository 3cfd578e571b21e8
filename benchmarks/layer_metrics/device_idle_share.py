"""device: share of the traced sub-window in which no operation ran on
the chip."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
