"""Host milliseconds per batch in the program span `repro.batch.dispatch`
of `BatchScheduler.step`: the call of the decode function (upload, length
check, enqueue), before the device is done."""

from lib.program_trace import ms_per_span


def read(run):
    return ms_per_span(run, "repro.batch.dispatch")
