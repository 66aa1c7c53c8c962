"""Host milliseconds per step in the program span `repro.inflight.commit` of
`InflightScheduler.step`: the commit scan over every slot that advanced."""

from lib.program_trace import ms_per_span


def read(run):
    return ms_per_span(run, "repro.inflight.commit")
