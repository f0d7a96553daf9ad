"""The readers of the program's own spans (``benchmarks/program_spans.py``
and the ``layer_metrics`` that use it) on hand-made span lists, and the
rehearsal that lists every one of them."""

import collections
import json
import os
import subprocess
import sys

import pytest

from benchmarks import program_spans as ps
from benchmarks import trace as trace_mod
from benchmarks.common import ROOT, load_module, read_json

Span = collections.namedtuple(
    "Span", "name start_ns end_ns tid thread trace_id span_id "
            "parent_span_id kind status error attrs events")

NEW = ["host_step_ms", "host_step_self_ms", "shard_batch_ms", "host_busy_pct",
       "save_slot_wait_ms", "save_copy_dispatch_ms", "save_submit_ms",
       "stage_s", "stage_d2h_wait_s", "stage_pace_sleep_s", "stage_compile_s",
       "stage_shm_copy_s", "stage_chunk_mib", "stage_unaccounted_pct",
       "idle_attributed_pct"]
TRAINER = NEW[:4]
MS = 1_000_000
T0 = 1_790_000_000 * 1_000_000_000     # the epoch's nanoseconds, as the ring has


def span(name, start_ms, dur_ms, span_id="", parent="", tid=1, **attrs):
    return Span(name, T0 + int(start_ms * MS), T0 + int((start_ms + dur_ms) * MS),
                tid, "t", "trace", span_id, parent, "internal", "ok", "",
                attrs, [])


def made_up(steps=6, save_at=3, with_save=True, stage=True):
    """``steps`` steps of 100 ms: 1 ms of shard_batch, a 4 ms step of which
    3 ms dispatch; after step ``save_at`` a save of 50 ms in three parts,
    and its stage of 2 s on another thread."""
    spans = [span("trainer.step", -500, 4, "old")]     # before the window
    for k in range(steps):
        at = 100 * k
        spans.append(span("trainer.shard_batch", at, 1, bytes=64))
        spans.append(span("trainer.step.dispatch", at + 2.5, 3, parent=f"s{k}"))
        spans.append(span("trainer.step", at + 2, 4, f"s{k}", step=k))
        if with_save and k == save_at:
            spans += [
                span("flash.save.slot_wait", at + 10, 5, parent="save"),
                span("flash.save.device_copy", at + 15, 40, parent="save"),
                span("flash.save.submit", at + 55, 2, parent="save"),
                span("flash.save", at + 10, 50, "save", step=k),
            ]
    if with_save and stage:
        spans.append(span(
            "flash.stage", 100 * save_at + 58, 2000, "stage", "save", tid=2,
            bytes=1 << 30, chunks=128, chunk_bytes_median=8 << 20,
            lock_wait_s=0.1, pace_sleep_s=0.5, slice_s=0.2, compile_s=0.15,
            compiles=3, d2h_wait_s=0.9, shm_copy_s=0.2))
    return spans


def observed(steps=6, save=True, **more):
    values = {"save_blocked_ms": 50.0} if save else {}
    return {"values": values, "attempted": steps + (1 if save else 0), **more}


def read(name, obs, spans, monkeypatch):
    monkeypatch.setattr(ps, "ring", lambda: spans)
    return load_module("layer_metrics", name).read(obs)


@pytest.mark.parametrize("name,want", [
    ("host_step_ms", 4.0), ("host_step_self_ms", 1.0),
    ("shard_batch_ms", 1.0),
    # 6 x (1 + 4) ms and the save's 50 over 506 ms
    ("host_busy_pct", 100 * 80 / 506),
    ("save_slot_wait_ms", 5.0), ("save_copy_dispatch_ms", 40.0),
    ("save_submit_ms", 2.0), ("stage_s", 2.0), ("stage_d2h_wait_s", 0.9),
    ("stage_pace_sleep_s", 0.5), ("stage_compile_s", 0.15),
    ("stage_shm_copy_s", 0.2), ("stage_chunk_mib", 8.0),
    ("stage_unaccounted_pct", 100 * (2.0 - 1.9) / 2.0),
])
def test_reader_on_made_up_spans(name, want, monkeypatch):
    assert read(name, observed(), made_up(), monkeypatch) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_empty_ring_reads_nothing(name, monkeypatch):
    obs = observed(trace_loaded=trace_mod.Trace({}, [], {}))
    assert read(name, obs, [], monkeypatch) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_recorder_reads_nothing(name, monkeypatch):
    """The parent commit's ring holds rendered records (dicts)."""
    # the package holds the module once anything has imported it
    import dlrover_tpu.observability as pkg

    monkeypatch.setitem(sys.modules, "dlrover_tpu.observability.flight_recorder",
                        None)
    monkeypatch.delattr(pkg, "flight_recorder", raising=False)
    assert ps.ring() == []
    obs = observed(trace_loaded=trace_mod.Trace({}, [], {}))
    assert load_module("layer_metrics", name).read(obs) is None


def test_ring_keeps_only_span_tuples(monkeypatch):
    class Recorder:
        spans = [{"name": "rpc.get/X", "ts": 1.0}, span("trainer.step", 0, 1)]

    import types

    fake = types.SimpleNamespace(recorder=lambda: Recorder)
    monkeypatch.setitem(
        sys.modules, "dlrover_tpu.observability.flight_recorder", fake)
    import dlrover_tpu.observability as pkg

    monkeypatch.setattr(pkg, "flight_recorder", fake, raising=False)
    assert [s.name for s in ps.ring()] == ["trainer.step"]


@pytest.mark.parametrize("name", NEW[4:14])
def test_an_evicted_save_reads_nothing(name, monkeypatch):
    """The ring turned over: the steps are there, the save is gone."""
    spans = [s for s in made_up() if not s.name.startswith("flash.save")]
    assert read(name, observed(), spans, monkeypatch) is None


@pytest.mark.parametrize("name", TRAINER)
def test_trainer_readers_need_no_save(name, monkeypatch):
    value = read(name, observed(save=False), made_up(with_save=False),
                 monkeypatch)
    assert value is not None and value > 0


def test_window_is_the_last_attempted_steps():
    window = ps.select(observed(steps=4), made_up(steps=6))
    assert [s.attrs["step"] for s in window.steps] == [2, 3, 4, 5]
    assert len(window.shard_batches) == 4 and len(window.dispatch) == 4
    assert window.save.attrs["step"] == 3 and window.stage.span_id == "stage"
    # a save before the window's first step is not the window's
    early = ps.select(observed(steps=2), made_up(steps=6))
    assert early.save is None and early.stage is None


def _harness(spans, origin_ns, first, n, pad_ms=0.05):
    """``bench.train_step`` of steps ``first..first+n``, each a little wider
    than the program's, in the xplane's seconds from ``origin_ns``."""
    steps = [s for s in spans if s.name == "trainer.step"][1:]
    return [("bench.train_step",
             (s.start_ns - pad_ms * MS - origin_ns) * 1e-9,
             (s.end_ns + pad_ms * MS - origin_ns) * 1e-9)
            for s in steps[first:first + n]]


def test_clock_offset_from_nesting():
    spans = made_up(steps=8)
    origin = T0 + 123_456_789
    harness = _harness(spans, origin, first=2, n=4)
    loaded = trace_mod.Trace({}, harness + [
        ("bench.save_checkpoint", (T0 + 309 * MS - origin) * 1e-9,
         (T0 + 361 * MS - origin) * 1e-9)], {})
    offset, slack, matched = ps.offset_for(
        observed(steps=8, trace_loaded=loaded), spans)
    assert matched == 4 and 0 <= slack <= 0.11 * MS
    assert abs(offset - origin) <= 0.05 * MS


def test_clock_offset_undecided_without_an_anchor_on_even_steps():
    """Steps exactly 100 ms apart fit at every shift: no answer is better
    than a wrong one."""
    spans = made_up(steps=8, with_save=False)
    harness = _harness(spans, T0, first=2, n=4)
    ring_steps = [s for s in spans if s.name == "trainer.step"][1:]
    assert ps.clock_offset_ns(ring_steps, [(s, e) for _, s, e in harness]) is None
    assert ps.clock_offset_ns(ring_steps[:3],
                              [(s, e) for _, s, e in harness]) is None
    assert ps.clock_offset_ns(ring_steps, []) is None


def test_idle_attributed_pct_on_another_origin(monkeypatch, capsys):
    spans = made_up(steps=8)
    origin = T0 - 5_000_000_000
    harness = _harness(spans, origin, first=2, n=4)

    def at(ms):
        return (T0 + ms * MS - origin) * 1e-9

    loaded = trace_mod.Trace(
        # busy but for 1 ms under step 3's shard_batch (300..301) and 2 ms
        # at 380..382, under no span of the stepping thread or the stager's
        device_ops={0: [("fusion", at(200), at(300.2)),
                        ("fusion", at(301.2), at(380)),
                        ("fusion", at(382), at(600))]},
        host_spans=sorted(harness + [
            ("bench.window", at(200), at(600)),
            ("bench.save_checkpoint", at(309), at(361))], key=lambda s: s[1]),
        seen={})
    # the stage would cover 380..382 too: leave it out to see a miss
    no_stage = [s for s in spans if s.name != "flash.stage"]
    obs = observed(steps=8, trace_loaded=loaded)
    got = read("idle_attributed_pct", obs, no_stage, monkeypatch)
    assert got == pytest.approx(100 * 1.0 / 3.0, rel=1e-3)
    line = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert line["phase"] == "program_spans" and line["steps_matched"] == 4
    assert abs(line["clock_offset_ns"] - origin) <= 0.05 * MS
    assert read("idle_attributed_pct", obs, spans, monkeypatch) == \
        pytest.approx(100.0)


def test_every_new_metric_is_registered_for_its_cells():
    """Present and in order: later PRs append metrics after them and cells
    to the trainer ones' ``workloads``."""
    entries = read_json(ROOT, "BENCHMARK.json")["per_layer"]
    mine = {m["name"]: m for m in entries if m["name"] in NEW}
    assert list(mine) == NEW        # in the issue's order
    for name, m in mine.items():
        assert m["moves"] == "tokens_per_s"
        if name in TRAINER:
            assert m["workloads"][:2] == ["mistral7b_l2.steady",
                                          "gpt2m.save_mem"]
        else:
            assert m["workloads"] == ["gpt2m.save_mem"]


def stats(start_ms, step, **lists):
    return span("trainer.model_stats", start_ms, 0.1, step=step, **lists)


MOE = {"moe_load_max_over_mean": "load_max_over_mean",
       "moe_rows_held_over_live": "rows_held_over_live",
       "moe_chip_rows_max_over_mean": "chip_rows_max_over_mean"}


@pytest.mark.parametrize("name", sorted(MOE))
def test_moe_counters_read_the_worst_layer_of_the_windows_records(
        name, monkeypatch, capsys):
    """Two records inside the window, one before it: the worst layer of
    the window's records, and the earlier record's 9.0 is not read."""
    spans = made_up(with_save=False) + [
        stats(-400, 0, **{attr: [9.0, 9.0] for attr in MOE.values()}),
        stats(105, 20, load_max_over_mean=[1.1, 1.3],
              rows_held_over_live=[1.25, 1.25],
              chip_rows_max_over_mean=[1.02, 1.04]),
        stats(405, 40, load_max_over_mean=[1.2, 1.15],
              rows_held_over_live=[1.25, 1.3125],
              chip_rows_max_over_mean=[1.07, 1.03]),
    ]
    want = {"moe_load_max_over_mean": 1.3, "moe_rows_held_over_live": 1.3125,
            "moe_chip_rows_max_over_mean": 1.07}[name]
    assert read(name, observed(save=False), spans, monkeypatch) == want
    capsys.readouterr()
    # a dense model sows nothing: nothing to read, never a 0
    assert read(name, observed(save=False), made_up(with_save=False),
                monkeypatch) is None


def test_the_extent_counter_is_registered_for_the_routed_cell():
    entries = read_json(ROOT, "BENCHMARK.json")["per_layer"]
    mine = next(m for m in entries if m["name"] == "moe_chip_rows_max_over_mean")
    assert mine == {"name": "moe_chip_rows_max_over_mean", "unit": "x",
                    "better": "lower", "source": "program_counter",
                    "layer": "trainer step", "moves": "tokens_per_s",
                    "workloads": ["olmoe1b7b_ep4.steady"]}
    fa2 = {m["name"]: m["workloads"] for m in entries
           if m["name"].startswith("fa2_")}
    assert all("gpt2m.save_mem" in cells for cells in fa2.values()) and fa2


def test_rehearsal_lists_every_new_metric():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "gpt2m.save_mem", "--seed", "3000000019",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = [line for line in proc.stdout.splitlines() if line.strip()]
    assert all(line.startswith("REHEARSAL ") for line in lines)
    last = json.loads(lines[-1][len("REHEARSAL "):])
    assert last["phase"] == "result" and last["correct"] is True
    assert set(NEW) | {"setup_wall_s"} <= set(last["would_print"])
