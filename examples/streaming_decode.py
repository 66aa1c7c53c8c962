"""Streaming decode: states become final while the sequence is still arriving.

    PYTHONPATH=src python examples/streaming_decode.py

Simulates a live feed (emission chunks arriving over time) against a
StreamSession, printing each committed prefix as it becomes final, then
verifies the assembled path is bit-identical to the offline decode.  The
second half shows the serving shape: a StreamMux carrying two concurrent
sessions with different latency/memory profiles (exact vs narrow beam).
"""

import time

import numpy as np
import jax

from repro.core import erdos_renyi_hmm, sample_observations, viterbi_vanilla
from repro.serving import StreamConfig, StreamSession, StreamMux

K, T, CHUNK = 64, 512, 32

key = jax.random.key(0)
k_hmm, k_obs = jax.random.split(key)
hmm = erdos_renyi_hmm(k_hmm, K, num_obs=50, edge_prob=0.253)
_, obs = sample_observations(k_obs, hmm, T)
em = np.asarray(hmm.emissions(obs))

print(f"live feed: K={K}, T={T}, {CHUNK}-frame chunks\n")
sess = StreamSession(hmm.log_pi, hmm.log_A, StreamConfig(), block=CHUNK)
t_open, first_commit_s = time.monotonic(), None
for start in range(0, T, CHUNK):
    committed = sess.feed(em[start:start + CHUNK])
    if committed.shape[0] and first_commit_s is None:
        first_commit_s = time.monotonic() - t_open
    n = sess.decoder.n_committed
    bar = "#" * (40 * n // T)
    print(f"  t={start + CHUNK:4d}  +{committed.shape[0]:3d} states final "
          f"(lag {sess.lag:3d}, live {sess.live_state_bytes():6d} B)  |{bar}")
path, score = sess.finish()

ref_path, ref_score = viterbi_vanilla(hmm.log_pi, hmm.log_A, em)
assert np.array_equal(path, np.asarray(ref_path))
first = (f"first commit after {first_commit_s * 1e3:.1f} ms"
         if first_commit_s is not None else "no commit before finish()")
print(f"\nassembled path == offline decode (score {score:.2f}); {first}\n")

print("two concurrent sessions, one mux (exact vs B=16 beam):")
mux = StreamMux(hmm.log_pi, hmm.log_A,
                StreamConfig(method="online_beam", beam_width=16, kchunk=64),
                blocks=(CHUNK,))
exact = StreamSession(hmm.log_pi, hmm.log_A, StreamConfig(), block=CHUNK)
sid = mux.open(block=CHUNK)
for start in range(0, T, CHUNK):
    exact.feed(em[start:start + CHUNK])
    mux.feed(sid, em[start:start + CHUNK])
p1, s1 = exact.finish()
p2, s2 = mux.finish(sid)
agree = float(np.mean(p1 == p2))
print(f"  exact   : score {s1:9.2f}, live state O(W*K)")
print(f"  beam 16 : score {s2:9.2f}, live state O(W*B) — "
      f"{100 * agree:.1f}% of states agree with exact")
