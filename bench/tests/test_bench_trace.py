"""The trace reduction, on a synthetic trace and on a small recorded one."""

import pytest

from lib import trace
from lib.spans import Spans, WINDOW

MS = 1_000_000  # ns


def _raw():
    """Two devices over a 100 ms window.  Device 0 runs the decode program
    (a 30 ms kernel and a 10 ms backtrack), idles 40 ms while the host is
    in `sched.step`, then runs a 5 ms op; device 1 runs 50 ms."""
    op = lambda n, s, e: (n, s * MS, e * MS, {})  # noqa: E731
    return {
        "host": [(WINDOW, 0, 100 * MS),
                 ("bench.sched.step", 40 * MS, 80 * MS),
                 ("bench.submit", 80 * MS, 90 * MS),
                 ("bench.sched.step", -50 * MS, -10 * MS)],
        "devices": {
            0: {"ops": [op("fwd_kernel", 0, 30), op("backtrack", 30, 40),
                        op("slice", 80, 85)],
                "modules": [op("jit__run_spec_batch", 0, 40),
                            op("jit_slice", 80, 85)]},
            1: {"ops": [op("fwd_kernel", 10, 60)],
                "modules": [op("jit__run_spec_batch", 10, 60)]},
            2: {"ops": [op("other", 0, 100)], "modules": []},
        }}


def test_reduce_busy_ops_modules_and_idle_by_phase():
    red = trace.reduce_events(_raw(), n_devices=2)
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx((0.045 + 0.050) / 2)
    assert red["op_s_total"]["fwd_kernel"] == pytest.approx(0.080)
    assert red["module_s_total"]["jit__run_spec_batch"] == pytest.approx(0.09)
    assert red["module_calls"]["jit__run_spec_batch"] == 2
    idle = red["idle_s_by_phase"]
    # device 0: 40 ms idle in sched.step, 5 ms in submit, 10 ms in none;
    # device 1: 20 ms in sched.step, 10 in submit, 20 in none (mean of two)
    assert idle["sched.step"] == pytest.approx((0.040 + 0.020) / 2)
    assert idle["submit"] == pytest.approx((0.005 + 0.010) / 2)
    assert idle["(no harness phase)"] == pytest.approx((0.010 + 0.020) / 2)
    bd = trace.breakdown(red)
    assert bd["device_ops"][0][0] == "fwd_kernel"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_only_the_chips_that_ran_an_op_count():
    raw = _raw()
    raw["devices"][1]["ops"] = [("fwd_kernel", -20 * MS, -10 * MS, {})]
    red = trace.reduce_events(raw, n_devices=2)
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(0.045)


def test_no_window_or_no_device_reads_as_nothing():
    assert trace.reduce_events({"host": [], "devices": {}})["devices"] == 0
    red = trace.reduce_events({"host": [(WINDOW, 0, MS)], "devices": {}})
    assert red["devices"] == 0 and red["busy_s"] == 0


def test_union_and_gaps():
    assert trace.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert trace.overlap([(0, 2), (4, 6)], [(1, 5)]) == 2


def test_recorded_trace_holds_the_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    spans = Spans(True)
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=trace.profile_options())
    with jax.profiler.TraceAnnotation(WINDOW):
        with spans("pump"):
            jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    assert path is not None
    raw = trace.load_events(path)
    names = {n for n, _, _ in raw["host"]}
    assert {WINDOW, "bench.pump"} <= names
    red = trace.reduce_events(raw)
    assert red["host_phase_s"]["pump"] > 0


def test_short_names_of_ops_and_programs():
    assert trace.short_name(
        "%viterbi_forward_batch.1 = (s32[64,512,512]) custom-call(f32[512]"
    ) == "%viterbi_forward_batch.1"
    assert trace.short_name("jit__unknown(17916418399187985263)") == (
        "jit__unknown")
    assert trace.short_name("jit_f") == "jit_f"


def test_decode_roofline_reads_the_decode_program_by_its_exact_name():
    from lib import readers
    run = {"counters": {"K": 512, "frames": 1000, "batches": 2},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"module_s_total": {"jit__unknown": 0.001,
                                        "jit__unknown_other": 5.0,
                                        "jit_local": 5.0}}}
    need = 4 * 512 * 1000 + 4 * 1000 + 4 * 512 * 512 * 2
    assert readers.decode_roofline(run) == pytest.approx(
        100.0 * need / 819e9 / 0.001)
    run["trace"]["module_s_total"] = {"jit_decode": 1.0}
    assert readers.decode_roofline(run) is None


def test_forward_kernel_op_is_matched_by_its_exact_name():
    from lib import readers
    run = {"counters": {"frames": 100},
           "trace": {"op_s_total": {"%viterbi_forward_batch.1": 1e-6,
                                    "%viterbi_forward_batch": 1e-6,
                                    "%viterbi_forward_batch_masked.2": 1.0,
                                    "%fusion.3": 1.0}}}
    assert readers.fwd_kernel_ns_per_frame(run) == pytest.approx(20.0)
