#!/usr/bin/env python3
"""Chip smoke test: drive the fused Viterbi decode path once on a TPU.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4     # four chips: the sharded decode only

Everything runs in this one process (a chip belongs to one process), through
the entry points a user calls, on inputs made from ``--seed``:

  (a) offline serving, K=512: `repro.launch.serve.main --method fused`,
      32 requests of T in 96..512 in buckets 128/256/512, batches of 8;
  (b) the inflight tier, K=512: `InflightScheduler` with 64 slots of 16
      frames, exact sessions fed to completion in uneven chunks;
  (c) constrained fused decode, K=512, B=4: `FusedSpec` with a
      `TransitionMaskConstraint`, which runs the masked kernel;
  (d) the paper's forced alignment, K=3965, T=256: the spec `plan` picks for
      a bucket of 8 under a 1 GiB budget (an XLA path by design).

Each phase runs twice on the same inputs: the cold pass compiles, the warm
pass is timed.  It then prints one ``phase {...}`` line: the spec, shapes,
compile seconds (jax's own lowering + compile time), run seconds (host
clock, results on the host), whether the compiled decode holds the Mosaic
kernel (``tpu_custom_call``), and how many paths were checked against the
numpy reference and were identical.  These are smoke wall times, not
benchmark numbers.  Phases (a)-(c) fail without the kernel; any mismatch or
error exits non-zero.  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``--chips 4`` decodes one fused K=512 bucket of 16 through the serving
head of a `DataPlacement` (`FusedSpec` on a 4-device ``data`` mesh, so
`ViterbiDecoder.decode_sharded`), compares it bit for bit with
`decode_batch` on one device, and prints where each output's shards live.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"


class SmokeFailure(AssertionError):
    """A phase produced a wrong or unexpected result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds jax spends lowering and compiling programs; cache hits.

    Tracing is left out: a jit traced inside another jit reports its own
    trace time nested in the outer one's, so summing it would count twice.
    """

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def cold_then_warm(self, fn):
        """Run `fn` cold, then warm; return both results and the timings."""
        s0, h0 = self.seconds, self.cache_hits
        t0 = time.perf_counter()
        cold = fn()
        cold_s = time.perf_counter() - t0
        s1, h1 = self.seconds, self.cache_hits
        t0 = time.perf_counter()
        warm = fn()
        warm_s = time.perf_counter() - t0
        return cold, warm, {"compile_s": s1 - s0, "cold_s": cold_s,
                            "run_s": warm_s,
                            "warm_compile_s": self.seconds - s1,
                            "cache_hits": h1 - h0}


def _compiled_text(jitted, *args, **static) -> str:
    return jitted.lower(*args, **static).compile().as_text()


def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _report(line: dict, *, need_kernel: bool) -> dict:
    print("phase " + json.dumps(line), flush=True)
    check(line["identical"] == line["checked"] > 0,
          f"phase {line['phase']}: {line['identical']}/{line['checked']} "
          f"paths identical to the numpy reference")
    if need_kernel:
        check(line["kernel"], f"phase {line['phase']}: compiled decode has "
              f"no tpu_custom_call (the Pallas kernel fell back to XLA)")
    return line


def _same_paths(a, b) -> bool:
    import numpy as np
    return len(a) == len(b) and all(np.array_equal(x, y) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# (a) offline serving through launch.serve
# ---------------------------------------------------------------------------

def phase_serve(clock: CompileClock, seed: int, *, states: int = 512,
                requests: int = 32, max_batch: int = 8,
                sample: int = 8) -> dict:
    import jax.numpy as jnp
    import numpy as np
    from repro.core.decoder import _jit_decode_batch
    from repro.core.reference import viterbi_numpy
    from repro.launch import serve

    argv = ["--method", "fused", "--states", str(states),
            "--requests", str(requests), "--max-batch", str(max_batch),
            "--seed", str(seed)]
    cold, warm, t = clock.cold_then_warm(lambda: serve.main(argv))
    check(_same_paths([r.result[0] for r in cold.done],
                      [r.result[0] for r in warm.done]),
          "serve: cold and warm passes served different paths")
    t["cold_s"], t["run_s"] = cold.wall_s, warm.wall_s   # the drain alone

    lp, la = np.asarray(warm.hmm.log_pi), np.asarray(warm.hmm.log_A)
    picked = warm.done[::max(1, len(warm.done) // sample)][:sample]
    same = sum(np.array_equal(viterbi_numpy(lp, la, r.payload)[0], r.result[0])
               for r in picked)
    Tb = max(serve.BUCKETS)
    text = _compiled_text(_jit_decode_batch(warm.spec),
                          _sds((max_batch, Tb, states), jnp.float32),
                          warm.hmm.log_pi, warm.hmm.log_A,
                          _sds((max_batch,), jnp.int32))
    lengths = sorted({len(r.payload) for r in warm.done})
    return _report({
        "phase": "a_serve", "spec": repr(warm.spec),
        "shapes": f"K={states} requests={len(warm.done)} T={lengths} "
                  f"buckets={list(serve.BUCKETS)} max_batch={max_batch} "
                  f"batches={warm.stats['batches']}",
        **t, "kernel": "tpu_custom_call" in text,
        "checked": len(picked), "identical": int(same)}, need_kernel=True)


# ---------------------------------------------------------------------------
# (b) the inflight slot-pool tier
# ---------------------------------------------------------------------------

def phase_inflight(clock: CompileClock, seed: int, *, K: int = 512,
                   slots: int = 64, block: int = 16, sessions: int = 40,
                   t_range: tuple[int, int] = (48, 320)) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import erdos_renyi_hmm
    from repro.core.reference import viterbi_numpy
    from repro.serving.inflight import InflightScheduler, _inflight_step

    hmm = erdos_renyi_hmm(jax.random.key(seed + 1), K)
    rng = np.random.default_rng(seed + 1)
    lengths = rng.integers(t_range[0], t_range[1] + 1, sessions)
    frames = [(2.0 * rng.standard_normal((int(T), K))).astype(np.float32)
              for T in lengths]
    chunks = [list(rng.integers(1, 3 * block + 1, int(T))) for T in lengths]

    def serve_all():
        sched = InflightScheduler(hmm.log_pi, hmm.log_A, max_slots=slots,
                                  block=block)
        sids = [sched.submit(max_lag=None) for _ in range(sessions)]
        pos = [0] * sessions
        paths: dict[int, np.ndarray] = {}
        rounds = 0
        while len(paths) < sessions:
            for i, sid in enumerate(sids):
                if i in paths:
                    continue
                if pos[i] < len(frames[i]):
                    c = int(chunks[i][rounds])
                    sched.feed(sid, frames[i][pos[i]:pos[i] + c])
                    pos[i] += c
                else:
                    paths[i] = sched.finish(sid)[0]
            sched.pump()
            rounds += 1
        return [paths[i] for i in range(sessions)], sched.stats["steps"]

    (cold, _), (warm, steps), t = clock.cold_then_warm(serve_all)
    check(_same_paths(cold, warm),
          "inflight: cold and warm passes decoded different paths")
    lp, la = np.asarray(hmm.log_pi), np.asarray(hmm.log_A)
    same = sum(np.array_equal(viterbi_numpy(lp, la, f)[0], p)
               for f, p in zip(frames, warm))
    text = _compiled_text(
        _inflight_step, hmm.log_pi, hmm.log_A, _sds((slots, K), jnp.float32),
        _sds((slots,), jnp.bool_), _sds((slots, block, K), jnp.float32),
        _sds((slots, K), jnp.float32), _sds((slots,), jnp.int32), bt=8)
    return _report({
        "phase": "b_inflight",
        "spec": f"InflightScheduler(max_slots={slots}, block={block}, "
                f"max_lag=None)",
        "shapes": f"K={K} sessions={sessions} T={int(lengths.min())}.."
                  f"{int(lengths.max())} chunks=1..{3 * block} steps={steps}",
        **t, "kernel": "tpu_custom_call" in text,
        "checked": sessions, "identical": int(same)}, need_kernel=True)


# ---------------------------------------------------------------------------
# (c) constrained fused decode (the masked kernel)
# ---------------------------------------------------------------------------

def phase_constrained(clock: CompileClock, seed: int, *, K: int = 512,
                      B: int = 4, T: int = 256, reach: int = 8) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import (FusedSpec, TransitionMaskConstraint,
                            ViterbiDecoder, erdos_renyi_hmm)
    from repro.core.constraints import constrain_inputs
    from repro.core.decoder import _jit_decode_batch
    from repro.core.reference import viterbi_numpy

    hmm = erdos_renyi_hmm(jax.random.key(seed + 2), K)
    # a ring of forward arcs: every state keeps a successor, so it is feasible
    edges = tuple((i, (i + d) % K) for i in range(K) for d in range(reach + 1))
    spec = FusedSpec(constraint=TransitionMaskConstraint(edges=edges))
    dec = ViterbiDecoder(spec, hmm.log_pi, hmm.log_A)
    rng = np.random.default_rng(seed + 2)
    em = (2.0 * rng.standard_normal((B, T, K))).astype(np.float32)
    lengths = np.asarray([T] + sorted(rng.integers(T // 4, T, B - 1)),
                         np.int32)

    def decode():
        return jax.device_get(dec.decode_batch(em, lengths))

    (cold, _), (paths, _), t = clock.cold_then_warm(decode)
    check(np.array_equal(cold, paths),
          "constrained: cold and warm passes decoded different paths")
    lp, la, em_m = (np.asarray(x) for x in constrain_inputs(
        spec.constraint, hmm.log_pi, hmm.log_A, jnp.asarray(em)))
    same = sum(np.array_equal(viterbi_numpy(lp, la, em_m[i, :L])[0],
                              paths[i, :L])
               for i, L in enumerate(lengths))
    text = _compiled_text(_jit_decode_batch(spec), _sds(em.shape, jnp.float32),
                          hmm.log_pi, hmm.log_A, _sds((B,), jnp.int32))
    return _report({
        "phase": "c_constrained",
        "spec": f"FusedSpec(bt={spec.bt}, constraint=TransitionMaskConstraint"
                f"({len(edges)} arcs))",
        "shapes": f"K={K} B={B} T={T} lengths={lengths.tolist()}",
        **t, "kernel": "tpu_custom_call" in text,
        "checked": B, "identical": int(same)}, need_kernel=True)


# ---------------------------------------------------------------------------
# (d) the paper's forced alignment, planned
# ---------------------------------------------------------------------------

def phase_alignment(clock: CompileClock, seed: int, *, K: int = 3965,
                    T: int = 256, B: int = 8, n_obs: int = 256,
                    n_check: int = 2, budget: int = 1 << 30) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import (ResourceBudget, ViterbiDecoder, left_to_right_hmm,
                            plan)
    from repro.core.decoder import _jit_decode_batch
    from repro.core.reference import viterbi_numpy

    hmm = left_to_right_hmm(jax.random.key(seed + 3), K, n_obs)
    decode_plan = plan(K, T, ResourceBudget(budget), batch=B)
    dec = ViterbiDecoder(decode_plan.spec, hmm.log_pi, hmm.log_A)
    rng = np.random.default_rng(seed + 3)
    em = (2.0 * rng.standard_normal((B, T, K))).astype(np.float32)
    lengths = np.asarray([T] + sorted(rng.integers(T // 2, T, B - 1)),
                         np.int32)

    def decode():
        return jax.device_get(dec.decode_batch(em, lengths))

    (cold, _), (paths, _), t = clock.cold_then_warm(decode)
    check(np.array_equal(cold, paths),
          "alignment: cold and warm passes decoded different paths")
    lp, la = np.asarray(hmm.log_pi), np.asarray(hmm.log_A)
    same = sum(np.array_equal(viterbi_numpy(lp, la, em[i, :L])[0],
                              paths[i, :L])
               for i, L in enumerate(lengths[:n_check]))
    text = _compiled_text(_jit_decode_batch(decode_plan.spec),
                          _sds(em.shape, jnp.float32), hmm.log_pi, hmm.log_A,
                          _sds((B,), jnp.int32))
    return _report({
        "phase": "d_alignment", "spec": repr(decode_plan.spec),
        "shapes": f"K={K} B={B} T={T} lengths={lengths.tolist()} "
                  f"plan='{decode_plan.why}'",
        **t, "kernel": "tpu_custom_call" in text,
        "checked": n_check, "identical": int(same)}, need_kernel=False)


# ---------------------------------------------------------------------------
# --chips 4: the bucket sharded over a data mesh
# ---------------------------------------------------------------------------

def phase_sharded(clock: CompileClock, seed: int, *, n_dev: int = 4,
                  K: int = 512, B: int = 16, T: int = 512) -> dict:
    import jax
    import numpy as np
    from repro.core import DataPlacement, FusedSpec, erdos_renyi_hmm
    from repro.serving.alignment import make_alignment_head

    placement = DataPlacement.over_devices(FusedSpec(), n_dev)
    hmm = erdos_renyi_hmm(jax.random.key(seed + 4), K)
    head = make_alignment_head(hmm.log_pi, hmm.log_A, placement)
    dec = head.decoder
    rng = np.random.default_rng(seed + 4)
    em = (2.0 * rng.standard_normal((B, T, K))).astype(np.float32)
    lengths = rng.integers(T // 4, T + 1, B).astype(np.int32)

    def sharded():
        return jax.block_until_ready(head(em, lengths))

    _, (s_paths, s_scores), t = clock.cold_then_warm(sharded)
    one_paths, one_scores = jax.device_get(dec.decode_batch(em, lengths))
    placement = {}
    for name, arr in (("paths", s_paths), ("scores", s_scores)):
        shards = sorted((s.device.id, s.data.shape)
                        for s in arr.addressable_shards)
        placement[name] = {"sharding": str(arr.sharding),
                           "shards": [f"dev{d}:{list(shp)}"
                                      for d, shp in shards]}
        print(f"sharding {name}: {placement[name]}", flush=True)
        check(len({d for d, _ in shards}) == n_dev
              and not arr.sharding.is_fully_replicated
              and all(shp[0] == B // n_dev for _, shp in shards),
              f"sharded {name} is not split over {n_dev} devices: "
              f"{placement[name]}")
    s_paths, s_scores = jax.device_get((s_paths, s_scores))
    bitwise = (s_paths.tobytes() == one_paths.tobytes()
               and s_scores.tobytes() == one_scores.tobytes())
    text = jax.jit(head).lower(em, lengths).compile().as_text()
    return _report({
        "phase": "sharded4", "spec": repr(dec.spec),
        "shapes": f"K={K} B={B} T={T} mesh=data:{n_dev}",
        **t, "kernel": "tpu_custom_call" in text,
        "checked": B, "identical": B if bitwise else 0}, need_kernel=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded decode over four chips")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repo's sources are not next to this script "
              f"(no {SRC / 'repro'})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but jax found platform "
              f"{dev.platform!r} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"jax found {len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)

    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(clock, args.seed)
    else:
        for phase in (phase_serve, phase_inflight, phase_constrained,
                      phase_alignment):
            phase(clock, args.seed)
    print(f"total {time.perf_counter() - t0:.1f}s, compile "
          f"{clock.seconds:.1f}s, persistent-cache hits {clock.cache_hits}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
