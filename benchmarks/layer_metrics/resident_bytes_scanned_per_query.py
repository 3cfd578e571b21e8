"""kernels: bytes of stored columns and validity a statement's fused-scan
launches were handed, per statement: ``resident_bytes_scanned``, counted
at the launch point from the stored buffers' widths and the splits'
padded rows (no device read; a tail batch's padded slots are not
counted, as ``splits_scanned`` does not count them). Read like
``program_launches``: a gauge of the last statement on the serial path,
a process total on the concurrent path. For a whole-table scan it is
harness/scanbytes' count plus a validity byte a slot, which is what
``scan_roofline`` divides. A program without the counter (and a cell
whose tables are generated) gives nothing to read."""

from benchmarks.harness.layers import per_statement


def read(ctx):
    return per_statement(ctx, "resident_bytes_scanned") or None
