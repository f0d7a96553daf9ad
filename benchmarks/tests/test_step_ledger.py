"""The readers of the window's steps one by one (``benchmarks/step_ledger.py``
and the six ``layer_metrics`` that use it) on hand-made span lists, their
place in ``BENCHMARK.json``, and the rehearsal that lists every one."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import program_spans as ps
from benchmarks import step_ledger
from benchmarks.common import ROOT, load_module, read_json
from benchmarks import trace as trace_mod
from benchmarks.tests.test_program_spans import (
    MS, T0, _harness, observed, span)

NEW = ["step_max_over_median", "stall_ms", "stall_program_ms", "host_tick_ms",
       "host_run_delay_pct", "gc_pause_ms"]
#: ``host_run_delay_pct`` has its reader and no entry: the benchmark's
#: machines run a sandboxed kernel with no ``schedstat`` (PERF.md, PR 53)
REGISTERED = [name for name in NEW if name != "host_run_delay_pct"]


def made_up(steps=41, period=100, stalls=None, account=True, ticks=True):
    """``steps`` steps whose closes lie ``period`` ms apart: 1 ms of
    shard_batch, a 4 ms step of which 3 ms dispatch, every twentieth step a
    tick of 2 ms inside it (the step is then 6 ms).  ``stalls``: window
    step -> (extra ms, where): ``tick`` stretches that step's tick (made if
    it has none), ``gc`` puts a collection of that length between the
    steps, ``outside`` nothing at all.  Every step but the first carries
    the account: 3 ms of CPU, 0.5 ms of run-queue delay, the collector's
    pauses."""
    stalls = stalls or {}
    spans = [span("trainer.step", -500, 4, "old", step=0)]
    close = 0.0
    for k in range(steps):
        extra, where = stalls.get(k, (0, None))
        close += period + extra if k else 10
        tick_ms = (2 if ticks and k and k % 20 == 0 else 0) + (
            extra if where == "tick" else 0)
        start = close - 4 - tick_ms
        spans.append(span("trainer.shard_batch", start - 2, 1, bytes=64))
        spans.append(span("trainer.step.dispatch", start + 0.5, 3,
                          parent=f"s{k}", compiled=False))
        if tick_ms:
            spans.append(span(
                "trainer.step.tick", start + 3.7, tick_ms, parent=f"s{k}",
                step=k, poll_s=0.0001, memscope_s=0.0005, digests_s=0.0002,
                write_s=1e-3 * (tick_ms - 1), stats_read_s=0.0002,
                stats_leaves=20, cpu_ns=2 * MS))
        gc_ns = 0
        if where == "gc":
            spans.append(span("runtime.gc", start - 10 - extra, extra,
                              generation=2, collected=7))
            gc_ns = int(extra * MS)
        attrs = {"step": k, "cpu_ns": 3 * MS}
        if account and k:
            attrs.update(interval_cpu_ns=3 * MS + gc_ns, run_delay_ns=MS // 2,
                         gc_ns=gc_ns + MS // 10, nvcsw=2, nivcsw=0, majflt=0)
        spans.append(span("trainer.step", start, 4 + tick_ms, f"s{k}",
                          **attrs))
    # the stager's, on another thread over the whole window
    spans.append(span("flash.stage.shard", 0, close, tid=2, path="w"))
    return spans


def read(name, spans, monkeypatch, steps=41):
    monkeypatch.setattr(ps, "ring", lambda: spans)
    return load_module("layer_metrics", name).read(
        observed(steps=steps, save=False))


CLEAN = made_up()
#: 900 ms lost under the tick of step 20, 400 under a collection before step
#: 30, 300 under nothing before step 35; step 10 is 50 ms late: no stall
STALLED = made_up(stalls={20: (900, "tick"), 30: (400, "gc"),
                          35: (300, "outside"), 10: (50, "outside")})


@pytest.mark.parametrize("name,want", [
    ("step_max_over_median", 1.0), ("stall_ms", 0.0),
    ("stall_program_ms", 0.0), ("host_tick_ms", 2.0),
    # 40 intervals of 0.5 ms over the 4000 ms their closes span
    ("host_run_delay_pct", 100 * 40 * 0.5 / 4000),
    ("gc_pause_ms", 40 * 0.1),
])
def test_reader_on_a_clean_window(name, want, monkeypatch, capsys):
    assert read(name, CLEAN, monkeypatch) == pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    ("step_max_over_median", 10.0),
    ("stall_ms", 900 + 400 + 300),
    # the tick's 900 and the collection's 400 are the program's, the bare
    # 300 are not (what a calm interval holds of the program is taken off)
    ("stall_program_ms", 900 + 400),
    ("host_tick_ms", (2 + 902) / 2),
    ("gc_pause_ms", 40 * 0.1 + 400),
])
def test_reader_on_a_stalled_window(name, want, monkeypatch, capsys):
    assert read(name, STALLED, monkeypatch) == pytest.approx(want, rel=2e-3)


@pytest.mark.parametrize("name", NEW)
def test_a_ring_without_the_account_reads_nothing(name, monkeypatch):
    """The parent commit: the same spans, none of the new attributes."""
    spans = made_up(account=False, ticks=False)
    assert read(name, spans, monkeypatch) is None


@pytest.mark.parametrize("name", NEW)
def test_an_empty_ring_reads_nothing(name, monkeypatch):
    assert read(name, [], monkeypatch) is None


def test_a_window_without_a_tick_reads_no_tick_and_the_rest(monkeypatch,
                                                             capsys):
    spans = made_up(steps=12)
    assert read("host_tick_ms", spans, monkeypatch, steps=12) is None
    assert read("stall_ms", spans, monkeypatch, steps=12) == 0.0
    assert read("step_max_over_median", spans, monkeypatch, steps=12) == 1.0


@pytest.mark.parametrize("lacking,name", [
    ("run_delay_ns", "host_run_delay_pct")])
def test_a_platform_without_a_source_reads_nothing_there(
        lacking, name, monkeypatch):
    spans = [s._replace(attrs={k: v for k, v in s.attrs.items()
                               if k != lacking}) for s in made_up()]
    assert read(name, spans, monkeypatch) is None
    assert read("gc_pause_ms", spans, monkeypatch) is not None


def test_the_window_is_the_last_attempted_steps(monkeypatch):
    """Ten steps before the window are not the window's: the ledger holds
    as many intervals as the window has steps less one."""
    monkeypatch.setattr(ps, "ring", lambda: STALLED)
    led = step_ledger.read(observed(steps=31, save=False))
    assert len(led.intervals_ns) == 30
    assert [s.attrs["step"] for s in led.closing] == list(range(11, 41))
    assert step_ledger.stall_ms(led) == pytest.approx(900 + 400 + 300)
    # and the tick of step 20, with the stalled one's length
    assert [t.attrs["step"] for t in step_ledger.ticks(led)] == [20, 40]


def test_the_line_holds_every_interval_and_the_stalls_explained(
        monkeypatch, capsys):
    assert read("step_max_over_median", STALLED, monkeypatch) == 10.0
    line = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert line["phase"] == "step_ledger" and line["steps"] == 41
    assert len(line["intervals_ms"]) == 40
    assert line["median_ms"] == 100.0
    assert [t["step"] for t in line["ticks"]] == [20, 40]
    assert line["ticks"][0]["ms"] == 902.0 and line["tick_max_ms"] == 902.0
    assert line["ticks"][0]["stats_leaves"] == 20
    assert line["tick_parts_median_ms"]["write_s"] == pytest.approx(451.0)
    assert set(line["tick_parts_median_ms"]) == set(step_ledger.TICK_PARTS)
    stalls = {s["window_step"]: s for s in line["stalls"]}
    assert sorted(stalls) == [20, 30, 35]
    for record in list(stalls.values()) + [line["longest"]]:
        # explain's parts sum to the interval
        assert record["parts_sum_over_interval"] == pytest.approx(1.0)
        assert sum(record["parts_ms"].values()) + record[
            "outside_spans_ms"] == pytest.approx(record["interval_ms"])
        # another thread's span beside it, summed into nothing
        assert record["others_ms"] == {
            "flash.stage.shard@t": record["interval_ms"]}
    assert line["longest"]["window_step"] == 20
    assert line["profiler_stop"] is None
    assert stalls[20]["parts_ms"]["trainer.step.tick"] == 902.0
    assert stalls[30]["parts_ms"]["runtime.gc"] == 400.0
    assert stalls[30]["gc_ns"] == 400 * MS + MS // 10
    assert stalls[35]["outside_spans_ms"] == pytest.approx(400 - 5, abs=1)
    assert stalls[35]["next_interval_ms"] == 100.0
    assert line["totals"]["run_delay_ns"] == 40 * MS // 2
    assert line["slow_steps"] == []      # the program made none here


def test_the_interval_that_holds_the_profilers_stop_is_the_harnesss(
        monkeypatch, capsys):
    """A traced run: the harness stops the profiler between steps 24 and 25,
    0.9 s on the stepping thread.  The trace's ``bench.window`` ends there;
    through the clocks' offset the interval is known and left out."""
    spans = made_up(stalls={25: (900, "outside"), 35: (300, "outside"),
                            # uneven steps, so that the clocks match one way
                            3: (7, "outside"), 17: (13, "outside")})
    origin = T0 + 987_654_321
    harness = _harness(spans, origin, first=5, n=20)   # steps 5..24 traced
    window = ("bench.window", harness[0][1] - 1e-3, harness[-1][2] + 2e-3)
    loaded = trace_mod.Trace({}, sorted(harness + [window],
                                        key=lambda s: s[1]), {})
    monkeypatch.setattr(ps, "ring", lambda: spans)
    obs = observed(steps=41, save=False, trace_loaded=loaded)
    led = step_ledger.read(obs)
    assert led.harness == 24 and led.closing[24].attrs["step"] == 25
    assert step_ledger.stall_ms(led) == pytest.approx(300)
    assert step_ledger.step_max_over_median(led) == pytest.approx(4.0)
    line = step_ledger.report(led)
    assert line["profiler_stop"]["interval_ms"] == 1000.0
    assert [s["window_step"] for s in line["stalls"]] == [35]
    assert line["longest"]["window_step"] == 35
    # an untraced run, or clocks that match nowhere: nothing is left out
    assert step_ledger.read(observed(steps=41, save=False)).harness is None
    nowhere = trace_mod.Trace({}, [window], {})
    led = step_ledger.read(observed(steps=41, save=False,
                                    trace_loaded=nowhere))
    assert led.harness is None
    assert step_ledger.stall_ms(led) == pytest.approx(900 + 300)


def test_the_programs_own_records_are_in_the_line_whole(monkeypatch, capsys):
    from dlrover_tpu.observability import flight_recorder

    recorder = flight_recorder.FlightRecorder(attach_log_handler=False)
    monkeypatch.setattr(flight_recorder, "_RECORDER", recorder)
    at = T0 * 1e-9
    record = {"step": 20, "interval_ms": 1002.0, "word": "program:x"}
    recorder.record_event({"ts": at - 100, "name": "trainer.slow_step",
                           "content": {"step": 3}})      # before the window
    recorder.record_event({"ts": at + 2, "name": "trainer.slow_step",
                           "content": record})
    recorder.record_event({"ts": at + 3, "name": "trainer.ckpt.save",
                           "content": {}})
    read("step_max_over_median", STALLED, monkeypatch)
    line = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert line["slow_steps"] == [record]


def test_every_registered_metric_is_there_for_all_nine_cells():
    bench = read_json(ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    entries = bench["per_layer"]
    mine = [m for m in entries if m["name"] in NEW]
    assert [m["name"] for m in mine] == REGISTERED    # in the issue's order
    sources = {"gc_pause_ms": "program_counter"}
    units = {"step_max_over_median": "x"}
    for m in mine:
        assert m == {
            "name": m["name"], "unit": units.get(m["name"], "ms"),
            "better": "lower",
            "source": sources.get(m["name"], "program_span"),
            "layer": "trainer step", "moves": "tokens_per_s",
            "workloads": m["workloads"]}
        assert m["workloads"][:9] == cells[:9]
    # appended: after the last entry that was there
    names = [m["name"] for m in entries]
    assert names.index(REGISTERED[0]) == names.index(
        "swa_pairs_multiplied_over_allowed") + 1


def test_rehearsal_lists_every_new_metric_and_prints_the_line():
    # one device, as the cell has; a tick every other step, so that a CPU's
    # short window holds some
    env = {**os.environ, "XLA_FLAGS": "", "DLROVER_TPU_DIGEST_EVERY": "2"}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "mistral7b_l2.steady", "--seed", "3000000019",
         "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = [line for line in proc.stdout.splitlines() if line.strip()]
    assert all(line.startswith("REHEARSAL ") for line in lines)
    last = json.loads(lines[-1][len("REHEARSAL "):])
    assert last["phase"] == "result" and last["correct"] is True
    assert set(REGISTERED) <= set(last["would_print"])
    (ledger,) = [json.loads(line) for line in proc.stderr.splitlines()
                 if line.startswith('{"phase": "step_ledger"')]
    # as many intervals as the window has steps less one
    assert len(ledger["intervals_ms"]) == last["attempted"] - 1
    assert ledger["ticks"] and "write_s" in ledger["ticks"][0]
    for record in ledger["stalls"] + [ledger["longest"]]:
        assert record["parts_sum_over_interval"] == pytest.approx(1, abs=0.01)
