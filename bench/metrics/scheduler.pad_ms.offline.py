"""Host milliseconds per batch in the program span `repro.batch.pad` of
`BatchScheduler.step`: picking a batch from the queue and padding it into
one (B, bucket, K) array."""

from lib.program_trace import ms_per_span


def read(run):
    return ms_per_span(run, "repro.batch.pad")
