"""Share of the decode kernel's rows whose input the chip gathered from a
table's arena mirror in HBM: the program's
``repro.decode.device_gather.rows`` over ``repro.decode.kernel_rows``
(rows requested) in the window, in percent.  Nothing is read where the
program does not count them; a window that ran no kernel row reads 0."""

from benchmarks.chip import program_spans

GATHERED = "repro.decode.device_gather.rows"
ROWS = "repro.decode.kernel_rows"


def read(ctx):
    if not program_spans.catalogued(GATHERED, ROWS):
        return None
    rows = ctx["counters"].get(ROWS, 0)
    return 100.0 * ctx["counters"].get(GATHERED, 0) / rows if rows else 0.0
