"""Share of the traced window spent faulting spilled blocks back in (the
program's ``repro.residency.fault_in`` span: the checked disk reads, the
re-append into the memory arena and the re-pointing of rows), in
percent."""

from benchmarks.chip import program_spans


def read(ctx):
    return program_spans.window_share(ctx, "repro.residency.fault_in")
