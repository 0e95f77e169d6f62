"""Share of the traced window spent spilling cold blocks to disk (the
program's ``repro.residency.spill`` span: one framed segment write of the
clock sweep's victims), in percent."""

from benchmarks.chip import program_spans


def read(ctx):
    return program_spans.window_share(ctx, "repro.residency.spill")
