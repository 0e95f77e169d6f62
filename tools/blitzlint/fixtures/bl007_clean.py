"""BL007 clean: a telemetry span."""

from repro import telemetry


def timed(work):
    with telemetry.span("repro.core.encode"):
        return work()
