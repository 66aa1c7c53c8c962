"""Host milliseconds per batch in the program span `repro.batch.unpad` of
`BatchScheduler.step`: the per-row fan-out of paths and scores to the
requests, after the device is done."""

from lib.program_trace import ms_per_span


def read(run):
    return ms_per_span(run, "repro.batch.unpad")
