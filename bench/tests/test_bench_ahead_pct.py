"""The reader of `scheduler.ahead_pct.offline`, on synthetic program
reductions: the share of dispatched batches that went out ahead."""

import pytest

from lib import registry

NAME = "scheduler.ahead_pct.offline"


def _run(span_n):
    return {"counters": {}, "trace": {},
            "program": {"span_s": dict.fromkeys(span_n, 0.1),
                        "span_n": span_n}}


@pytest.mark.parametrize("ahead, dispatch, value", [
    (40, 40, 100.0), (0, 40, 0.0), (None, 40, 0.0), (39, 40, 97.5),
    (None, None, None), (3, 0, None)])
def test_ahead_share_reads_ahead_spans_over_dispatch_spans(ahead, dispatch,
                                                           value):
    metric = registry.load_metric(NAME)
    span_n = {"repro.batch.pad": 40, "repro.batch.unpad": 40}
    for name, n in (("repro.batch.ahead", ahead),
                    ("repro.batch.dispatch", dispatch)):
        if n is not None:
            span_n[name] = n
    assert metric.read(_run(span_n)) == value


def test_ahead_share_reads_nothing_without_a_program_trace():
    metric = registry.load_metric(NAME)
    assert metric.read({"counters": {}, "trace": {}, "program": {}}) is None
    assert metric.read({"counters": {}, "trace": {}}) is None


def test_ahead_share_is_listed_with_both_offline_cells():
    bench = registry.load_benchmark()
    listed = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert listed["workloads"] == ["default_k512.offline",
                                   "default_k512_dp4.offline"]
    assert listed["moves"] == "frames_per_s"
    assert listed["source"] == "program_span"
