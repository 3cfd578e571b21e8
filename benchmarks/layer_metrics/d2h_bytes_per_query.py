"""transfer: bytes copied device to host per statement: the process
total ``d2h_bytes`` of /metrics at both ends of the window over the
statements completed in it."""


def read(ctx):
    n = len(ctx["samples"])
    if not n or "d2h_bytes" not in ctx["metrics_end"]:
        return None
    return (ctx["metrics_end"]["d2h_bytes"]
            - ctx["metrics_start"]["d2h_bytes"]) / n
