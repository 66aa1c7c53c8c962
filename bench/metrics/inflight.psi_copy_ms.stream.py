"""Host milliseconds per step in the program span `repro.inflight.psi_copy`
of `InflightScheduler.step`: `np.asarray(psi)`, the wait for the kernel and
the copy of the (slots, block, K) int32 backpointers to the host."""

from lib.program_trace import ms_per_span


def read(run):
    return ms_per_span(run, "repro.inflight.psi_copy")
