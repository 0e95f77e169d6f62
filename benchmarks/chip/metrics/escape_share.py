"""Share of the rows read that decoded on the escape path: the program's
``repro.core.decode.scalar_rows`` (rows of escaped blocks, decoded value by
value on the host) over those plus ``repro.core.decode.rows`` (rows through
the slot plan's batched decode) in the window, in percent.  Nothing is read
where the program does not count them; a window that decoded no row reads
0."""

from benchmarks.chip import program_spans

SCALAR = "repro.core.decode.scalar_rows"
PLANNED = "repro.core.decode.rows"


def read(ctx):
    if not program_spans.catalogued(SCALAR, PLANNED):
        return None
    scalar = ctx["counters"].get(SCALAR, 0)
    total = scalar + ctx["counters"].get(PLANNED, 0)
    return 100.0 * scalar / total if total else 0.0
