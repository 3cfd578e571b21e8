"""executor: stored slots a statement's join builds read, per
statement: ``join_build_rows``, counted where a stored join's lookup
structure is built (the build tables' sizes, from shapes: no device
read). A program without the counter, or a statement that built
nothing, gives nothing to read."""

from benchmarks.harness.layers import per_statement


def read(ctx):
    return per_statement(ctx, "join_build_rows") or None
