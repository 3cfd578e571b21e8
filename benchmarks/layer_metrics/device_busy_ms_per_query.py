"""kernels: device time per statement: the union of the device-operation
intervals of the recorded stretch over the statements that ran in it,
each counted by the share of its latency that fell inside the stretch
(the stretch is a few seconds and cuts statements at both ends)."""


def read(ctx):
    trace = ctx["trace"]
    n = sum(share for _st, share in ctx["traced_statements"])
    if trace is None or not n:
        return None
    return trace["busy_s"] * 1e3 / n
