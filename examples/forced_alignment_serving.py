"""Forced-alignment serving: batched alignment requests through the serving head.

    PYTHONPATH=src python examples/forced_alignment_serving.py

An acoustic model upstream of this head turns each utterance into per-frame
log-posteriors over the states of its transcription's left-to-right HMM.
No model runs here: seeded log-softmax emissions of shape (T, K) stand in
for its output, as in `launch/serve.py`.  They are served as a deployment
serves them: `make_alignment_head` with a FLASH-BS beam behind
`BatchScheduler`, which buckets ragged lengths and pads them as
tropical-identity steps.  Every alignment is checked to move only forward
through the states, and its score against an exact decode.
"""

import sys
import time

import numpy as np
import jax

from repro.core import (FlashBSSpec, VanillaSpec, ViterbiDecoder,
                        left_to_right_hmm, relative_error)
from repro.serving.alignment import make_alignment_head
from repro.serving.scheduler import BatchScheduler

K = 64                       # HMM states of the transcription

# 1. the alignment HMM over the transcription's states (left-to-right)
hmm = left_to_right_hmm(jax.random.key(1), K, 16)

# 2. the serving head: FLASH-BS alignment behind the batching scheduler
head = make_alignment_head(hmm.log_pi, hmm.log_A,
                           FlashBSSpec(beam_width=32, parallelism=4,
                                       lanes=None))
sched = BatchScheduler(head, max_batch=4, buckets=(64,))

# 3. requests: seeded per-frame state log-posteriors of ragged lengths
rng = np.random.default_rng(0)
for _ in range(12):
    T = int(rng.integers(40, 64))
    logits = 2.0 * rng.standard_normal((T, K)).astype(np.float32)
    sched.submit(np.asarray(jax.nn.log_softmax(logits, axis=-1)))

t0 = time.time()
done = sched.drain()
wall = time.time() - t0
print(f"served {len(done)} alignment requests in {wall:.2f}s "
      f"({len(done)/wall:.1f} req/s) in {sched.stats['batches']} batches, "
      f"pad frac {sched.pad_frac():.2f}")
for r in done[:3]:
    path, score = r.result
    print(f"  req {r.rid}: frames={len(r.payload)} "
          f"alignment[0:12]={path[:12].tolist()} score={score:.1f}")

exact = ViterbiDecoder(VanillaSpec(), hmm.log_pi, hmm.log_A)
errs = [float(relative_error(exact.decode(r.payload)[1], r.result[1]))
        for r in done]
print(f"relative score error vs exact decode: mean {np.mean(errs):.2e}, "
      f"max {np.max(errs):.2e}")
monotone = all(np.all(np.diff(r.result[0]) >= 0) for r in done)
print("alignment paths are monotone:", monotone)
sys.exit(0 if monotone else 1)
