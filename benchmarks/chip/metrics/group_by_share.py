"""Share of the traced window in the Python group-by of aggregate queries:
the program's ``repro.db.aggregate`` span less the ``repro.scan.scan_table``
spans inside it, floored at 0, in percent."""

from benchmarks.chip import program_spans


def read(ctx):
    got = program_spans.window_seconds(ctx, "repro.db.aggregate",
                                       "repro.scan.scan_table")
    if got is None:
        return None
    aggregate, scan = got
    return 100.0 * max(0.0, aggregate - scan) / ctx["window_s"]
