"""Share of the rows the decode kernel ran that a caller asked for: the
program's ``repro.decode.kernel_rows`` (rows requested) over
``repro.decode.kernel_rows_padded`` (rows after the pow2 bucket pad) in
the window, in percent.  Nothing is read where the program does not count
them; a window that ran no kernel row reads 0."""

from benchmarks.chip import program_spans

ROWS = "repro.decode.kernel_rows"
PADDED = "repro.decode.kernel_rows_padded"


def read(ctx):
    if not program_spans.catalogued(ROWS, PADDED):
        return None
    padded = ctx["counters"].get(PADDED, 0)
    return 100.0 * ctx["counters"].get(ROWS, 0) / padded if padded else 0.0
