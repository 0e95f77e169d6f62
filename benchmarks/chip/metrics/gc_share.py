"""Share of the traced window in CPython's collector (the program's
``repro.runtime.gc`` span, one per collection), in percent."""

from benchmarks.chip import program_spans


def read(ctx):
    return program_spans.window_share(ctx, "repro.runtime.gc")
