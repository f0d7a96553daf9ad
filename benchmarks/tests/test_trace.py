"""The trace reduction: interval arithmetic on plain lists, and the whole
of it on the small trace recorded on the chip."""

import os

import pytest

from benchmarks import trace
from benchmarks.common import load_module

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tiny_step.xplane.pb")


def test_union_clip_gaps():
    busy = trace.union([(0, 1), (0.5, 2), (3, 4), (3.5, 3.75)])
    assert busy == [(0, 2), (3, 4)]
    assert trace.total(busy) == 3
    assert trace.clip(busy, (1, 3.5)) == [(1, 2), (3, 3.5)]
    assert trace.gaps(busy, (-1, 5)) == [(-1, 0), (2, 3), (4, 5)]


def _made_up():
    return trace.Trace(
        device_ops={0: [("fusion.1", 1.0, 2.0), ("kernel_a", 2.5, 3.0),
                        ("fusion.1", 3.0, 3.5)],
                    1: [("fusion.1", 1.0, 1.5)]},
        host_spans=[("bench.window", 0.0, 4.0),
                    ("bench.train_step", 0.0, 1.0),
                    ("bench.read_back", 2.0, 4.0)],
        seen={},
    )


def test_reduce_made_up_trace():
    out = trace.reduce(_made_up())
    assert out["window_s"] == 4.0
    assert out["busy_by_chip"] == {0: 2.0, 1: 0.5}
    assert out["busy_s"] == 1.25
    assert out["idle_pct_worst_chip"] == 100 * (1 - 0.5 / 4.0)
    # the worst chip is chip 1: one operation, idle before it under the
    # train_step span and after it under the read-back (and no span)
    assert out["device_ops"] == [["fusion.1", 0.5]]
    gaps = dict(out["idle_gaps"])
    assert gaps["bench.train_step"] == 1.0
    assert gaps["bench.read_back"] == 2.5


def test_window_falls_back_to_the_device_when_clocks_differ():
    made = _made_up()
    made.host_spans = [("bench.window", 100.0, 104.0)]
    assert trace.window_of(made) == (1.0, 3.5)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_recorded_trace():
    """Three steps of a one-layer model with the FA2 kernel on one TPU v5e
    (benchmarks/tests/record_trace.py; my chip run, PR 24)."""
    loaded = trace.load(RECORDED)
    assert list(loaded.device_ops) == [0]
    out = trace.reduce(loaded)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert 0 <= out["idle_pct_worst_chip"] < 100
    assert 1 <= len(out["device_ops"]) <= 10
    names = {s[0] for s in loaded.host_spans}
    assert {"bench.window", "bench.train_step", "bench.read_back"} <= names
    found = load_module("layer_metrics", "fa2_ms_per_step").kernel_events(
        {"trace_loaded": loaded})
    # one layer, three steps: forward twice a step (remat), dQ and dK/dV once
    assert {k: n for k, (n, _) in found.items()} == {
        "fwd": 6, "dq": 3, "dkv": 3}
    assert all(seconds > 0 for _, seconds in found.values())
    assert not any(name.startswith("%while") for name, _ in out["device_ops"])


def test_label_keeps_name_result_and_kind():
    text = ("%fusion.470 = (bf16[4096]{0:T(1024)(128)(2,1)}, f32[2,2048]{1,0}) "
            "fusion(bf16[2,2048,4096]{2,1,0} %copy-done.12), kind=kOutput")
    assert trace.label(text) == "%fusion.470 -> (bf16[4096], f32[2,2048]) fusion"
    assert trace.label("%copy.1") == "%copy.1"
