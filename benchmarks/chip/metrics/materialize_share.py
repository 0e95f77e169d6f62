"""Share of the traced window spent turning decoded symbols into row dicts
(the program's ``repro.decode.materialize`` span, point reads and scans
alike), in percent."""

from benchmarks.chip import program_spans


def read(ctx):
    return program_spans.window_share(ctx, "repro.decode.materialize")
