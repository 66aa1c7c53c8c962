"""Share of the batches dispatched in the window that `BatchScheduler.step`
dispatched ahead, while another batch was in flight: the count of program
spans `repro.batch.ahead` over that of `repro.batch.dispatch`, which every
batch opens once (a look-ahead batch inside its `repro.batch.ahead`).
0.0 where batches were dispatched and none ahead; nothing where no batch
was dispatched."""

from lib.program_trace import for_run

AHEAD, DISPATCH = "repro.batch.ahead", "repro.batch.dispatch"


def read(run):
    n = for_run(run).get("span_n", {})
    if not n.get(DISPATCH):
        return None
    return 100.0 * n.get(AHEAD, 0) / n[DISPATCH]
