"""Host milliseconds per batch in the program span `repro.decode.put` of
`ViterbiDecoder.decode_sharded`, inside `repro.batch.dispatch`: the put of
the padded batch and its lengths, each device sent its own rows."""

from lib.program_trace import ms_per_span


def read(run):
    return ms_per_span(run, "repro.decode.put")
