"""executor: the part of set-up that no cache saves: Python tracing of
every program to a jaxpr and lowering of the jaxpr to an MLIR module,
summed over the serving process up to the window's start
(``program_trace_wall_s`` + ``program_lower_wall_s`` of /metrics, read
after warm-up). Statements warm side by side, so the sum over threads
can pass the wall time of set-up. A program without the split gives
nothing to read."""


def read(ctx):
    m = ctx["metrics_start"]
    if "program_trace_wall_s" not in m or "program_lower_wall_s" not in m:
        return None
    return m["program_trace_wall_s"] + m["program_lower_wall_s"]
