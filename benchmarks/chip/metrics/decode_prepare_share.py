"""Share of the traced window in the host side of a kernel decode before
its result is awaited (the program's ``repro.decode.prepare`` span: the
gather and pad of the codes, the host-to-device copy and the kernel's
dispatch), in percent."""

from benchmarks.chip import program_spans


def read(ctx):
    return program_spans.window_share(ctx, "repro.decode.prepare")
