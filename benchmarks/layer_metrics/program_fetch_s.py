"""executor: the part of set-up spent getting executables: reading and
loading them from the persistent cache, and compiling what it does not
hold (0 in a warm run), summed over the serving process up to the
window's start (``program_retrieval_wall_s`` + ``compile_wall_s`` of
/metrics, read after warm-up). A program without the split gives
nothing to read."""


def read(ctx):
    m = ctx["metrics_start"]
    if "program_retrieval_wall_s" not in m or "compile_wall_s" not in m:
        return None
    return m["program_retrieval_wall_s"] + m["compile_wall_s"]
