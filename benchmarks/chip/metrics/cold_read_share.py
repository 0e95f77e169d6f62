"""Blocks faulted in from the cold tier per row decoded in the window:
the program's ``repro.residency.fault_in.blocks`` over
``repro.core.decode.rows`` (rows through the slot plan's batched decode)
plus ``repro.core.decode.scalar_rows`` (rows of escaped blocks), in
percent.  A block holds one row, so this is the share of reads that
missed memory.  Nothing is read where the program does not count these;
a window that decoded no row reads 0."""

from benchmarks.chip import program_spans

FAULTS = "repro.residency.fault_in.blocks"
PLANNED = "repro.core.decode.rows"
SCALAR = "repro.core.decode.scalar_rows"


def read(ctx):
    if not program_spans.catalogued(FAULTS, PLANNED, SCALAR):
        return None
    rows = ctx["counters"].get(PLANNED, 0) + ctx["counters"].get(SCALAR, 0)
    return 100.0 * ctx["counters"].get(FAULTS, 0) / rows if rows else 0.0
