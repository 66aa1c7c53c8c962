"""Host milliseconds per step in the program span `repro.inflight.dispatch`
of `InflightScheduler.step`: the uploads of the staged block and the call
of the slot-step kernel."""

from lib.program_trace import ms_per_span


def read(run):
    return ms_per_span(run, "repro.inflight.dispatch")
