"""Share of the traced window spent awaiting a kernel decode's result (the
program's ``repro.decode.wait`` span: the device's time left after the
dispatch and the copy back), in percent."""

from benchmarks.chip import program_spans


def read(ctx):
    return program_spans.window_share(ctx, "repro.decode.wait")
