"""The reduction of the program's own spans, and its metrics."""

import pytest

from lib import program_trace as pt
from lib import registry, trace
from lib.spans import WINDOW, Spans

MS = 1_000_000  # ns


def _raw():
    """One device over a 100 ms window.  The host is in `sched.step` from
    10 to 90 ms: the program pads (10-20), dispatches (20-25), waits
    (25-60) and unpads (60-90); the device runs the decode 30-50 ms and a
    row slice inside the unpad, 70-72 ms."""
    op = lambda n, s, e: (n, s * MS, e * MS, {})  # noqa: E731
    raw = {
        "host": [(WINDOW, 0, 100 * MS),
                 ("bench.sched.step", 10 * MS, 90 * MS),
                 ("bench.submit", 90 * MS, 95 * MS)],
        "devices": {0: {"ops": [op("fwd", 30, 45),
                                op("bt", 45, 50),
                                op("slice", 70, 72)],
                        "modules": [op("jit__unknown", 30, 50)]}}}
    prog = [("repro.batch.pad", 10 * MS, 20 * MS),
            ("repro.batch.dispatch", 20 * MS, 25 * MS),
            ("repro.batch.wait", 25 * MS, 60 * MS),
            ("repro.batch.unpad", 60 * MS, 90 * MS),
            ("repro.outer", 5 * MS, 98 * MS)]
    return raw, prog


def test_idle_goes_to_the_innermost_span_and_sums_to_its_phase():
    raw, prog = _raw()
    red = trace.reduce_events(raw, 1)
    out = pt.reduce_program(raw, prog, 1)
    idle = out["idle_s_by_span"]
    assert idle["sched.step/repro.batch.pad"] == pytest.approx(0.010)
    assert idle["sched.step/repro.batch.dispatch"] == pytest.approx(0.005)
    assert idle["sched.step/repro.batch.wait"] == pytest.approx(0.015)
    assert idle["sched.step/repro.batch.unpad"] == pytest.approx(0.028)
    assert idle["submit/repro.outer"] == pytest.approx(0.005)
    assert idle["(no harness phase)/repro.outer"] == pytest.approx(0.008)
    assert idle["(no harness phase)"] == pytest.approx(0.007)
    assert idle["sched.step"] == 0
    assert "sched.step/repro.outer" not in idle
    for phase, total in red["idle_s_by_phase"].items():
        parts = sum(v for k, v in idle.items()
                    if k == phase or k.startswith(phase + "/"))
        assert parts == pytest.approx(total), phase


def _two_devices(first_idle: bool):
    """`_raw` with a second device running the decode 40-55 ms; device 0
    runs nothing in the window where `first_idle`."""
    raw, prog = _raw()
    raw["devices"][1] = {"ops": [("fwd", 40 * MS, 55 * MS, {})],
                         "modules": []}
    if first_idle:
        raw["devices"][0]["ops"] = [("fwd", 120 * MS, 130 * MS, {})]
    return raw, prog


@pytest.mark.parametrize("first_idle, n_devices", [
    (False, None), (False, 1), (False, 2), (True, None), (True, 1),
    (True, 2)])
def test_two_devices_are_chosen_as_the_harness_chooses(first_idle,
                                                       n_devices):
    raw, prog = _two_devices(first_idle)
    red = trace.reduce_events(raw, n_devices)
    out = pt.reduce_program(raw, prog, n_devices)
    assert out["devices"] == red["devices"]
    for phase, total in red["idle_s_by_phase"].items():
        parts = sum(v for k, v in out["idle_s_by_span"].items()
                    if k == phase or k.startswith(phase + "/"))
        assert parts == pytest.approx(total), phase


def test_for_run_leaves_out_the_idle_split_of_other_devices(monkeypatch):
    raw, prog = _two_devices(False)
    monkeypatch.setattr(pt, "reduce_trace",
                        lambda: pt.reduce_program(raw, prog))
    same = {"trace": trace.reduce_events(raw, 2)}
    assert "idle_s_by_span" in pt.for_run(same)
    # the harness reduced one chip of the two that ran an op
    fewer = {"trace": trace.reduce_events(raw, 1)}
    prog_fewer = pt.for_run(fewer)
    assert "idle_s_by_span" not in prog_fewer
    assert prog_fewer["span_n"] == same["program"]["span_n"]


def test_span_seconds_and_counts_are_clipped_to_the_window():
    raw, prog = _raw()
    prog.append(("repro.batch.pad", -20 * MS, 5 * MS))
    prog.append(("repro.batch.pad", 120 * MS, 130 * MS))
    out = pt.reduce_program(raw, prog)
    assert out["span_n"]["repro.batch.pad"] == 2
    assert out["span_s"]["repro.batch.pad"] == pytest.approx(0.015)
    assert out["span_s"]["repro.outer"] == pytest.approx(0.093)


def test_existing_reduction_reads_the_same_beside_program_events():
    raw, prog = _raw()
    before = trace.reduce_events(raw, 1)
    pt.reduce_program(raw, prog, 1)
    assert trace.reduce_events(raw, 1) == before
    assert trace.breakdown(trace.reduce_events(raw, 1)) == trace.breakdown(
        before)


def test_recorded_trace_keeps_program_spans_apart(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.runtime.spans import span
    spans = Spans(True)
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=trace.profile_options())
    with jax.profiler.TraceAnnotation(WINDOW):
        with spans("sched.step"):
            with span("batch.pad", batch=7):
                jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    raw = trace.load_events(path)
    assert {n for n, _, _ in raw["host"]} == {WINDOW, "bench.sched.step"}
    prog = pt.load_program(path)
    assert [n for n, _, _ in prog] == ["repro.batch.pad"]
    red = pt.reduce_trace(tmp_path)
    assert red["span_n"] == {"repro.batch.pad": 1}


def test_steps_inside_the_finish_phase_are_counted():
    raw = {"host": [(WINDOW, 0, 100 * MS), ("bench.pump", 0, 10 * MS),
                    ("bench.finish", 10 * MS, 50 * MS)],
           "devices": {}}
    step = lambda s, e: ("repro.inflight.dispatch", s * MS, e * MS)  # noqa
    prog = [step(1, 2), step(11, 12), step(20, 22), step(48, 55),
            ("repro.inflight.commit", 30 * MS, 31 * MS)]
    out = pt.reduce_program(raw, prog)
    assert out["steps"] == 4
    assert out["finish_steps"] == 2


def test_a_window_without_a_trace_reads_nothing(tmp_path):
    assert pt.reduce_trace(tmp_path) == {}
    assert pt.reduce_program({"host": [], "devices": {}}, []) == {}
    # a run whose own reduction found no window reads no profile on disk
    assert pt.for_run({"counters": {}, "trace": {"devices": 0}}) == {}


PROGRAM = {"span_s": {"repro.batch.pad": 0.02, "repro.batch.dispatch": 0.01,
                      "repro.batch.unpad": 0.4,
                      "repro.inflight.dispatch": 0.03,
                      "repro.inflight.psi_copy": 0.05,
                      "repro.inflight.commit": 0.02},
           "span_n": {"repro.batch.pad": 2, "repro.batch.dispatch": 2,
                      "repro.batch.unpad": 2, "repro.inflight.dispatch": 10,
                      "repro.inflight.psi_copy": 10,
                      "repro.inflight.commit": 10},
           "steps": 10, "finish_steps": 9}


@pytest.mark.parametrize("name, value", [
    ("scheduler.pad_ms.offline", 10.0),
    ("scheduler.dispatch_ms.offline", 5.0),
    ("scheduler.unpad_ms.offline", 200.0),
    ("inflight.dispatch_ms.stream", 3.0),
    ("inflight.psi_copy_ms.stream", 5.0),
    ("inflight.commit_ms.stream", 2.0),
    ("inflight.finish_step_pct.stream", 90.0),
])
def test_program_metrics_read_a_value_or_nothing(name, value):
    metric = registry.load_metric(name)
    run = {"counters": {"frames": 1000}, "trace": {}, "program": PROGRAM}
    assert metric.read(run) == pytest.approx(value)
    # a trace of a program without the spans reads nothing
    assert metric.read({"counters": {"frames": 1000}, "trace": {},
                        "program": {}}) is None
    assert metric.read({"counters": {}, "trace": {},
                        "program": {"span_s": {}, "span_n": {},
                                    "steps": 0}}) is None


def test_every_program_metric_is_listed_with_its_cell():
    bench = registry.load_benchmark()
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in ("scheduler.pad_ms.offline", "inflight.commit_ms.stream",
                 "inflight.finish_step_pct.stream"):
        assert listed[name]["workloads"]
        assert registry.load_metric(name).read
