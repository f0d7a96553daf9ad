"""Which steps are slow and what the host was doing in them (PR 53):
``flight_recorder.explain`` on made-up rings, the collector's hook, the
account a ``trainer.step`` takes at its close, the tick's span with its
parts, and the ``trainer.slow_step`` record for a stall planted in each of
four places."""

import gc
import itertools
import json
import os
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.observability import flight_recorder, trace
from dlrover_tpu.trainer import step_account
from dlrover_tpu.utils.step_clock import StepClock

MS = 1_000_000
T0 = 1_790_000_000 * 1_000_000_000


def ns(ms):
    return round(ms * MS)


def span(name, start_ms, dur_ms, tid=1, thread="main", **attrs):
    return trace.SpanTuple(
        name, T0 + int(start_ms * MS), T0 + int((start_ms + dur_ms) * MS),
        tid, thread, "", "", "", "internal", "ok", "", attrs, [])


def explained(spans, start_ms, end_ms, tid=1):
    return flight_recorder.explain(
        T0 + int(start_ms * MS), T0 + int(end_ms * MS), tid, spans)


#: one slow interval, 0..1000 ms of thread 1: a batch, a step whose dispatch
#: and tick nest in it, a collection inside the tick, a stage on another
#: thread over most of it, and spans before and after that only touch it
RING = [
    span("trainer.step", -50, 60),                    # its last 10 ms
    span("trainer.shard_batch", 100, 20),
    span("trainer.step.dispatch", 210, 30),
    span("runtime.gc", 400, 50),
    span("trainer.step.tick", 300, 600),
    span("trainer.step", 200, 750),
    span("flash.stage.shard", 50, 300, tid=2, thread="ckpt-stager"),
    span("flash.stage", 0, 5000, tid=2, thread="ckpt-stager"),
    span("trainer.shard_batch", 990, 30),             # its first 10 ms
    span("trainer.step", 2000, 10),                   # after it
    {"name": "rpc.get/X", "ts": 1.0, "type": "SPAN"},  # a rendered record
]


class TestExplain:
    @pytest.mark.parametrize("name,want_ms", [
        ("trainer.shard_batch", 20 + 10),
        ("trainer.step.dispatch", 30),
        ("runtime.gc", 50),
        ("trainer.step.tick", 600 - 50),
        # the step's self time: its own 750 less dispatch and tick, and the
        # tail of the step before
        ("trainer.step", 10 + 750 - 30 - 600),
    ])
    def test_nested_spans_by_self_time(self, name, want_ms):
        assert explained(RING, 0, 1000)["parts_ns"][name] == want_ms * MS

    def test_parts_and_outside_sum_to_the_interval(self):
        found = explained(RING, 0, 1000)
        assert found["interval_ns"] == 1000 * MS
        assert sum(found["parts_ns"].values()) + found[
            "outside_spans_ns"] == 1000 * MS
        # 10..100, 120..200 and 950..990 lie under no span of the thread
        assert found["outside_spans_ns"] == (90 + 80 + 40) * MS

    def test_another_threads_spans_are_listed_not_summed(self):
        found = explained(RING, 0, 1000)
        assert found["others_ns"] == {
            "flash.stage@ckpt-stager": 1000 * MS,
            "flash.stage.shard@ckpt-stager": 300 * MS}
        assert not any(name.startswith("flash.")
                       for name in found["parts_ns"])
        # and from the stager's side the stepping thread's are the others
        stager = explained(RING, 0, 1000, tid=2)
        assert stager["parts_ns"] == {"flash.stage": 700 * MS,
                                      "flash.stage.shard": 300 * MS}
        assert stager["outside_spans_ns"] == 0

    @pytest.mark.parametrize("start_ms,end_ms", [
        (0, 1000), (205, 215), (-100, 3000), (950, 980), (399, 451)])
    def test_any_cut_is_a_partition(self, start_ms, end_ms):
        found = explained(RING, start_ms, end_ms)
        assert sum(found["parts_ns"].values()) + found[
            "outside_spans_ns"] == found["interval_ns"] == (
                end_ms - start_ms) * MS
        assert all(ns > 0 for ns in found["parts_ns"].values())

    def test_an_empty_ring_is_all_outside(self):
        found = explained([], 0, 10)
        assert found == {"interval_ns": 10 * MS, "parts_ns": {},
                         "outside_spans_ns": 10 * MS, "others_ns": {}}

    def test_the_recorders_own_ring_is_the_default(self, monkeypatch):
        recorder = flight_recorder.FlightRecorder(attach_log_handler=False)
        monkeypatch.setattr(flight_recorder, "_RECORDER", recorder)
        before = time.time_ns()
        with trace.span("trainer.step"):
            time.sleep(0.01)
        found = flight_recorder.explain(
            before, time.time_ns(), threading.get_ident())
        assert found["parts_ns"]["trainer.step"] >= 10 * MS
        assert sum(found["parts_ns"].values()) + found[
            "outside_spans_ns"] == found["interval_ns"]


#: among a case's parts: its interval in ms, where that is not 1000
INTERVAL = "(interval)"


class TestVerdict:
    @pytest.mark.parametrize("parts,cpu,delay,gc_ns,want", [
        ({"trainer.step.tick": 600, "trainer.step": 100}, 50, 0, 0,
         "program:trainer.step.tick"),
        ({"flash.save.device_copy": 900}, 800, 0, 0,
         "program:flash.save.device_copy"),
        # a collection inside the tick is the collector's, not the tick's
        ({"trainer.step.tick": 100, "runtime.gc": 700}, 800, 0, 720, "gc"),
        ({"runtime.gc": 700}, 800, 0, 720, "gc"),
        ({"trainer.step": 3}, 5, 600, 0, "runnable_not_run"),
        ({"trainer.step": 3}, 950, 10, 0, "caller_cpu"),
        ({"trainer.step": 3}, 5, 10, 0, "waiting"),
        ({}, 0, 0, 0, "waiting"),
        # the busy loop of the driver's run of PR 58's tree, six saturated
        # workers beside it: it never slept, was on a CPU for half of its
        # 404.553 ms and in the run queue for the other half, and neither
        # alone holds half; then the same with the two the other way round
        ({INTERVAL: 404.553, "trainer.step": 1.428}, 202.44, 200.917, 0,
         "caller_cpu"),
        ({INTERVAL: 404.553, "trainer.step": 1.428}, 202.345, 201.012, 0,
         "runnable_not_run"),
        # and a thread that did sleep: the two together under half
        ({INTERVAL: 404.553, "trainer.step": 1.428}, 102.44, 100.917, 0,
         "waiting"),
    ])
    def test_one_word(self, parts, cpu, delay, gc_ns, want):
        assert step_account.verdict(
            ns(parts.get(INTERVAL, 1000)),
            {k: ns(v) for k, v in parts.items() if k is not INTERVAL},
            ns(cpu), ns(delay), ns(gc_ns)) == want


@pytest.fixture
def rec(monkeypatch):
    """A private recorder, its log ring fed as the process's is."""
    from dlrover_tpu.common.log import logger

    recorder = flight_recorder.FlightRecorder(attach_log_handler=True)
    monkeypatch.setattr(flight_recorder, "_RECORDER", recorder)
    trace.set_span_sink(lambda record: None)
    yield recorder
    trace.set_span_sink(None)
    logger.removeHandler(recorder._log_handler)


def _named(recorder, name):
    return [t for t in recorder.spans
            if type(t) is not dict and t.name == name]


class _Graph:
    """A large graph of containers the collector has to walk."""

    def __init__(self, n):
        was = gc.isenabled()
        gc.disable()       # the making of it is not what is measured
        try:
            self.nodes = [[i] for i in range(n)]
            for a, b in zip(self.nodes, self.nodes[1:]):
                a.append(b)
        finally:
            if was:
                gc.enable()


class TestCollectorHook:
    def test_installed_once_with_the_process_recorder(self):
        flight_recorder.recorder()
        flight_recorder.recorder()
        assert gc.callbacks.count(flight_recorder._on_gc) == 1

    def test_a_long_pause_is_summed_and_is_a_span_on_its_thread(
            self, rec, monkeypatch):
        """The hook is handed its times, as ``test_one_word`` hands
        ``verdict`` its numbers: how long a collection takes is the
        host's, and what the hook makes of a long one is what is held."""
        flight_recorder.recorder()
        long_ns = 2 * flight_recorder.GC_SPAN_MIN_NS
        clock = itertools.count(T0, long_ns)    # every read a pause later
        before = flight_recorder.gc_pause_ns()
        was = gc.isenabled()
        gc.disable()       # no collection but the one asked for
        try:
            with monkeypatch.context() as patch:
                patch.setattr(
                    flight_recorder, "time", types.SimpleNamespace(**{
                        **vars(time), "time_ns": lambda: next(clock)}))
                gc.collect()
        finally:
            if was:
                gc.enable()
        paused = flight_recorder.gc_pause_ns() - before
        pauses = [s for s in _named(rec, "runtime.gc") if s.start_ns == T0]
        (pause,) = pauses
        assert pause.tid == threading.get_ident()
        assert pause.attrs["generation"] == 2
        assert pause.attrs["collected"] >= 0
        assert paused == pause.end_ns - pause.start_ns == long_ns
        # kept in memory as the step's spans are: a tuple, no record
        assert type(pause) is trace.SpanTuple
        # and a root of its own, so that an incident's timeline, which
        # holds the ring's spans to connected trees, takes it
        from dlrover_tpu.observability import timeline

        forest = timeline.span_forest(
            trace.record_of(s) for s in pauses)
        assert len(forest) == len(pauses)
        assert all(t["connected"] for t in forest.values())

    def test_a_short_pause_is_summed_and_no_span(self, rec):
        flight_recorder.recorder()
        gc.collect()          # what is left is little
        before, spans = flight_recorder.gc_pause_ns(), len(rec.spans)
        gc.collect(0)
        assert flight_recorder.gc_pause_ns() > before
        assert len(rec.spans) == spans

    def test_another_threads_pauses_are_its_own(self):
        flight_recorder.recorder()
        mine = flight_recorder.gc_pause_ns()
        seen = {}

        def elsewhere():
            before = flight_recorder.gc_pause_ns()
            gc.collect()
            seen["paused"] = flight_recorder.gc_pause_ns() - before
            seen["tid"] = threading.get_ident()

        thread = threading.Thread(target=elsewhere)
        thread.start()
        thread.join(60)
        assert not thread.is_alive() and seen["paused"] > 0
        assert flight_recorder.gc_pause_ns() == mine
        assert flight_recorder.gc_pause_ns(seen["tid"]) >= seen["paused"]


class _Events:
    def __init__(self):
        self.seen = []

    def instant(self, name, content):
        self.seen.append((name, content))


ACCOUNT_ATTRS = {"interval_cpu_ns", "run_delay_ns", "nvcsw", "nivcsw",
                 "majflt", "gc_ns"}


class TestAccountAtClose:
    def _two_steps(self, account):
        for step in (1, 2):
            with trace.span("trainer.step", attrs={"step": step}) as sp:
                until = time.thread_time() + 0.003    # its own CPU clock
                while time.thread_time() < until:
                    pass
                account.close(sp, step)

    def test_the_second_step_carries_the_interval(self, rec):
        account = step_account.StepAccount(StepClock(), _Events())
        self._two_steps(account)
        first, second = _named(rec, "trainer.step")
        assert not ACCOUNT_ATTRS & set(first.attrs)   # nothing before it
        assert ACCOUNT_ATTRS <= set(second.attrs)
        assert second.attrs["interval_cpu_ns"] >= 1_000_000   # it spun 3 ms
        assert all(second.attrs[k] >= 0 for k in ACCOUNT_ATTRS)
        # a per-step span reads no CPU clock of its own: the account's one
        # read a step covers it
        assert "cpu_ns" not in second.attrs

    @pytest.mark.parametrize("lacking,absent", [
        ("schedstat", {"run_delay_ns"}),
        ("rusage_thread", {"nvcsw", "nivcsw", "majflt"}),
        ("thread_clock", {"interval_cpu_ns"}),
        ("all", {"run_delay_ns", "nvcsw", "nivcsw", "majflt",
                 "interval_cpu_ns"}),
    ])
    def test_a_platform_without_a_source_leaves_it_out(
            self, rec, monkeypatch, tmp_path, lacking, absent):
        if lacking in ("schedstat", "all"):
            monkeypatch.setattr(step_account, "_SCHEDSTAT",
                                str(tmp_path / "no-such-file"))
        if lacking in ("rusage_thread", "all"):
            monkeypatch.setattr(step_account, "_RUSAGE_THREAD", None)
        if lacking in ("thread_clock", "all"):
            monkeypatch.setattr(step_account, "_thread_time_ns", None)
            monkeypatch.setattr(trace, "_thread_time_ns", None)
        events = _Events()
        clock = StepClock()
        for _ in range(4):
            clock.record(1e-4)    # any interval here is a slow one
        account = step_account.StepAccount(clock, events)
        for step in (1, 2, 3):
            with trace.span("trainer.step", attrs={"step": step}) as sp:
                time.sleep(0.06)
                account.close(sp, step)
            with trace.span("trainer.step.tick") as tick:
                pass
        last = _named(rec, "trainer.step")[-1]
        assert not absent & set(last.attrs)
        assert ACCOUNT_ATTRS - absent <= set(last.attrs)
        # and a span that would time its thread's CPU carries no ``cpu_ns``
        assert ("cpu_ns" in _named(rec, "trainer.step.tick")[-1].attrs) == (
            "interval_cpu_ns" not in absent)
        # and the record of a slow step is made of what there is
        (name, record), = events.seen
        assert name == "trainer.slow_step" and record["step"] == 2
        assert ("run_delay_ms" in record) == ("run_delay_ns" not in absent)
        assert ("cpu_ms" in record) == ("interval_cpu_ns" not in absent)
        assert ("nivcsw" in record) == ("nivcsw" not in absent)

    @pytest.mark.parametrize("baseline_s,sleep_s,slow", [
        (1e-3, 0.02, False),    # twenty baselines, but 20 ms: nobody's line
        (1e-3, 0.07, True),
        (0.1, 0.16, False),     # under two baselines
        (0.1, 0.21, False),     # two steps in one: a loop that drained
        (0.1, 0.3, True),
    ])
    def test_slow_is_over_twice_the_baseline_and_50_ms(
            self, rec, baseline_s, sleep_s, slow):
        events, clock = _Events(), StepClock()
        for _ in range(4):
            clock.record(baseline_s)
        account = step_account.StepAccount(clock, events)
        for step in (1, 2, 3):
            with trace.span("trainer.step", attrs={"step": step}) as sp:
                if step == 2:
                    time.sleep(sleep_s)
                account.close(sp, step)
        assert [c["step"] for _, c in events.seen] == [2] * slow

    def test_a_span_still_open_on_another_thread_is_listed(self, rec):
        started, done = threading.Event(), threading.Event()

        def stager():
            with trace.span("flash.stage"):
                started.set()
                done.wait(60)

        thread = threading.Thread(target=stager, name="ckpt-stager")
        thread.start()
        try:
            assert started.wait(60)
            t0 = time.time_ns()
            time.sleep(0.02)
            found = flight_recorder.explain(
                t0, time.time_ns(), threading.get_ident())
        finally:
            done.set()
            thread.join(60)
        assert not thread.is_alive()
        assert found["others_ns"] == {
            "flash.stage@ckpt-stager": found["interval_ns"]}
        assert found["outside_spans_ns"] == found["interval_ns"]

    def test_a_kernel_that_counts_no_switches_is_asked_eight_times(
            self, rec, monkeypatch):
        """A sandboxed kernel's ``getrusage`` reads zeros at a price: a
        thread none of whose switches it has counted stops asking."""
        calls = []

        class Resource:
            @staticmethod
            def getrusage(who):
                calls.append(who)
                return type("U", (), {
                    "ru_nvcsw": 0, "ru_nivcsw": 0, "ru_majflt": 0})()

        monkeypatch.setattr(step_account, "resource", Resource)
        account = step_account.StepAccount(StepClock(), _Events())
        for step in range(1, 14):
            with trace.span("trainer.step", attrs={"step": step}) as sp:
                account.close(sp, step)
        # the thread's first close, then the eight intervals of probation
        assert len(calls) == 1 + step_account.PROBATION
        steps = _named(rec, "trainer.step")
        assert {"nvcsw", "nivcsw", "majflt"} <= set(steps[8].attrs)
        assert not {"nvcsw", "nivcsw", "majflt"} & set(steps[-1].attrs)
        assert {"gc_ns", "interval_cpu_ns"} <= set(steps[-1].attrs)

    def test_a_kernel_that_counts_switches_is_asked_for_good(self, rec):
        account = step_account.StepAccount(StepClock(), _Events())
        for step in range(1, 2 * step_account.PROBATION + 2):
            with trace.span("trainer.step", attrs={"step": step}) as sp:
                time.sleep(0.001)        # a voluntary switch a step
                account.close(sp, step)
        last = _named(rec, "trainer.step")[-1]
        assert ACCOUNT_ATTRS <= set(last.attrs) and last.attrs["nvcsw"] >= 1
        assert "cpu_intervals" not in last.attrs   # a fine clock: every step

    def test_a_clock_that_ticks_every_10_ms_is_read_where_it_tells(
            self, rec, monkeypatch):
        """The benchmark's machines: after probation the thread's CPU clock
        is read on the tick and at a slow interval's close, over the
        intervals since its last reading; what the calm ones took comes
        off a slow one's."""
        reads = []
        real = time.thread_time_ns

        def coarse():
            reads.append(1)
            return real() // 10_000_000 * 10_000_000

        monkeypatch.setattr(step_account, "_thread_time_ns", coarse)
        events, clock = _Events(), StepClock()
        for _ in range(4):
            clock.record(0.02)
        account = step_account.StepAccount(clock, events)

        def step(n, tick=False, spin_s=0.003, sleep_s=0.0):
            with trace.span("trainer.step", attrs={"step": n}) as sp:
                until = real() + int(spin_s * 1e9)   # its own CPU clock
                while real() < until:
                    pass
                time.sleep(sleep_s)
                account.close(sp, n, tick)

        probation = 1 + step_account.PROBATION
        for n in range(1, probation + 1):
            step(n)
        assert len(reads) == probation          # every step so far
        for n in range(probation + 1, probation + 11):
            step(n)
        assert len(reads) == probation          # and none since
        step(probation + 11, tick=True)
        assert len(reads) == probation + 1
        ticked = _named(rec, "trainer.step")[-1].attrs
        assert ticked["cpu_intervals"] == 11
        assert ticked["interval_cpu_ns"] % 10_000_000 == 0
        assert 20_000_000 <= ticked["interval_cpu_ns"] <= 60_000_000
        assert "interval_cpu_ns" not in _named(rec, "trainer.step")[-2].attrs
        # four calm steps, then one that sleeps 0.2 s: slow by the wall, so
        # the clock is read at its close; it spun 3 ms like the others
        for n in range(probation + 12, probation + 16):
            step(n)
        step(probation + 16, sleep_s=0.2)
        assert len(reads) == probation + 2
        slow = _named(rec, "trainer.step")[-1].attrs
        assert slow["cpu_intervals"] == 5
        assert slow["interval_cpu_ns"] <= 20_000_000   # the calm four taken off
        step(probation + 17)
        (name, record), = events.seen
        # (the sleep was inside the span here)
        assert record["word"] == "program:trainer.step"
        assert record["cpu_intervals"] == 5
        assert record["cpu_ms"] <= 20.0 and record["interval_ms"] >= 200

    def test_a_schedstat_that_reads_garbage_raises_nothing(
            self, rec, monkeypatch, tmp_path):
        path = tmp_path / "schedstat"
        path.write_text("nonsense\n")
        monkeypatch.setattr(step_account, "_SCHEDSTAT", str(path))
        account = step_account.StepAccount(StepClock(), _Events())
        self._two_steps(account)
        assert "run_delay_ns" not in _named(rec, "trainer.step")[-1].attrs

    def test_another_stepping_thread_opens_its_own_schedstat(self, rec):
        account = step_account.StepAccount(StepClock(), _Events())
        self._two_steps(account)
        thread = threading.Thread(target=self._two_steps, args=(account,))
        thread.start()
        thread.join(60)
        assert not thread.is_alive()
        steps = _named(rec, "trainer.step")
        assert steps[2].tid != steps[1].tid
        # the first close on a thread has no interval of that thread's
        assert not ACCOUNT_ATTRS & set(steps[2].attrs)
        assert ACCOUNT_ATTRS <= set(steps[3].attrs)
        assert account._fd is not None and account._tid == steps[3].tid

    def test_switched_off_tracing_costs_no_account(self, monkeypatch):
        from dlrover_tpu.trainer.train import Trainer

        closes = []
        trainer = Trainer.__new__(Trainer)
        trainer._step_calls = 0
        trainer._ticked = False
        trainer._step_on_host = lambda state, batch: ("state", {})
        trainer._step_account = type(
            "A", (), {"close": lambda self, sp, n, tick: closes.append(n)})()
        monkeypatch.setenv("DLROVER_TPU_TRACE", "0")
        trace.seed_ids(0)
        try:
            assert trainer.train_step(None, None) == ("state", {})
            assert closes == []
        finally:
            monkeypatch.delenv("DLROVER_TPU_TRACE")
            trace.seed_ids(0)
        trainer.train_step(None, None)
        assert closes == [2]


class TestHostPressure:
    def test_reads_what_is_readable_as_integers(self):
        found = step_account.host_pressure()
        assert set(found) <= {"nr_throttled", "throttled_us",
                              "pressure_cpu_us", "pressure_io_us",
                              "pressure_memory_us"}
        assert all(isinstance(v, int) and v >= 0 for v in found.values())

    @pytest.mark.parametrize("stat,want", [
        ("usage_usec 5\nnr_throttled 3\nthrottled_usec 1234\n",
         {"nr_throttled": 3, "throttled_us": 1234}),
        ("nr_periods 9\nnr_throttled 2\nthrottled_time 7000999\n",
         {"nr_throttled": 2, "throttled_us": 7000}),
    ])
    def test_both_cgroup_versions_and_pressure(
            self, monkeypatch, tmp_path, stat, want):
        (tmp_path / "cpu.stat").write_text(stat)
        (tmp_path / "cpu").write_text(
            "some avg10=1.00 avg60=0.50 avg300=0.10 total=4242\n"
            "full avg10=0.00 avg60=0.00 avg300=0.00 total=7\n")
        monkeypatch.setattr(step_account, "_CPU_STAT",
                            (str(tmp_path / "absent"),
                             str(tmp_path / "cpu.stat")))
        monkeypatch.setattr(step_account, "_PRESSURE", str(tmp_path) + "/")
        assert step_account.host_pressure() == {
            **want, "pressure_cpu_us": 4242}

    def test_nothing_readable_reads_nothing(self, monkeypatch, tmp_path):
        monkeypatch.setattr(step_account, "_CPU_STAT", ())
        monkeypatch.setattr(step_account, "_PRESSURE",
                            str(tmp_path / "none") + "/")
        assert step_account.host_pressure() == {}


# -- a real trainer, stalls planted by hand ----------------------------------


class _Loop:
    """A tiny trainer stepped as a job steps it: one step in flight, the
    loss of the step before read back."""

    def __init__(self):
        from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.trainer.train import Trainer

        cfg = LlamaConfig.tiny()
        self.trainer = Trainer(
            LlamaForCausalLM(cfg), optax.adamw(1e-2),
            build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1]))
        ids = np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=(8, 33))
        self.host = {"input_ids": np.asarray(ids[:, :-1], np.int32),
                     "labels": np.asarray(ids[:, 1:], np.int32)}
        self.state = self.trainer.create_state(
            jax.random.PRNGKey(0), self.host["input_ids"])
        self.pending = None

    def step(self, between=None):
        """One turn of the loop; ``between`` runs in the caller, after the
        dispatch and before the next.  Returns the ``trainer.step``'s
        number."""
        self.state, metrics = self.trainer.train_step(
            self.state, self.trainer.shard_batch(self.host))
        if self.pending is not None:
            float(jax.device_get(self.pending))
        self.pending = metrics["loss"]
        if between is not None:
            between()
        return self.trainer._step_calls


@pytest.fixture(scope="module")
def loop(tmp_path_factory):
    """One trainer for the module (its compile is the cost).  The module's
    environment: a digest file of its own, the tick every 20 steps."""
    saved = {k: os.environ.get(k) for k in (
        "DLROVER_TPU_RUNTIME_METRICS_PATH", "DLROVER_TPU_DIGEST_EVERY",
        "DLROVER_TPU_COMM_PROBE_EVERY")}
    os.environ["DLROVER_TPU_RUNTIME_METRICS_PATH"] = str(
        tmp_path_factory.mktemp("digest") / "runtime_metrics.json")
    os.environ["DLROVER_TPU_DIGEST_EVERY"] = "20"
    os.environ["DLROVER_TPU_COMM_PROBE_EVERY"] = "0"
    trace.seed_ids(0)
    made = _Loop()
    for _ in range(6):       # the compile, then a calm baseline
        made.step()
    yield made
    for key, value in saved.items():
        if value is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = value


STALL_S = 0.4


def _slow_records(recorder, since=0):
    return [e["content"] for e in list(recorder.events)[since:]
            if e.get("name") == "trainer.slow_step"
            and e["content"]["interval_ms"] >= 0.5e3 * STALL_S]


def _plant(loop, rec, between=None, in_step=None):
    """Calm steps, one interval with the stall, steps after it.  Returns
    the record, having seen that it came one step late and once.
    ``between`` runs in the caller after a step's close; ``in_step`` is a
    context the stalled step itself runs under."""
    for _ in range(3):
        loop.step()
    since = len(rec.events)       # what the test's own set-up cost is past
    if in_step is not None:
        with in_step:
            slow_step = loop.step()
    else:
        loop.step(between)
        slow_step = loop.step()      # its close ends the stalled interval
    assert _slow_records(rec, since) == []  # not yet: one step late
    loop.step()
    records = _slow_records(rec, since)
    loop.step()
    loop.step()
    assert _slow_records(rec, since) == records and len(records) == 1  # once
    (record,) = records
    assert record["step"] == slow_step
    assert record["interval_ms"] >= 1e3 * STALL_S
    assert record["interval_ms"] > 2 * record["baseline_ms"]
    total = sum(record["parts_ms"].values()) + record["outside_spans_ms"]
    assert total == pytest.approx(record["interval_ms"], rel=0.01)
    assert record["next_interval_ms"] < record["interval_ms"] / 2
    assert list(record)[-1] == "word"
    # one WARNING line in the program's log, so in the recorder's log ring
    lines = [line for line in rec.logs
             if f"trainer.slow_step step={slow_step} " in line]
    assert len(lines) == 1 and " WARNING " in lines[0]
    assert lines[0].endswith("word=" + record["word"])
    json.dumps(record)
    return record


class TestSlowStepRecord:
    def test_a_sleep_in_the_digest_write(self, loop, rec, monkeypatch):
        """``program:trainer.step.tick``, and the stall under ``write_s``."""
        while loop.trainer._digest_steps % 20 != 16:
            loop.step()      # the fourth step from here is a tick's
        real = os.replace

        def slow_replace(src, dst):
            if "runtime_metrics" in str(dst):
                time.sleep(STALL_S)
            return real(src, dst)

        class Planted:
            def __enter__(self):
                monkeypatch.setattr(os, "replace", slow_replace)

            def __exit__(self, *exc):
                monkeypatch.setattr(os, "replace", real)

        record = _plant(loop, rec, in_step=Planted())
        assert record["word"] == "program:trainer.step.tick"
        assert record["parts_ms"]["trainer.step.tick"] >= 0.9e3 * STALL_S
        assert record["cpu_ms"] < 0.5 * record["interval_ms"]
        (tick,) = [t for t in _named(rec, "trainer.step.tick")
                   if t.attrs["write_s"] >= 0.9 * STALL_S]
        assert tick.attrs["step"] % 20 == 0
        assert tick.attrs["cpu_ns"] < 0.5 * (tick.end_ns - tick.start_ns)

    def test_a_sleep_in_the_caller_between_steps(self, loop, rec):
        record = _plant(loop, rec, between=lambda: time.sleep(STALL_S))
        assert record["word"] == "waiting"
        assert record["outside_spans_ms"] >= 0.9e3 * STALL_S
        assert record["cpu_ms"] < 0.25 * record["interval_ms"]
        assert record["gc_ms"] < 0.25 * record["interval_ms"]

    def test_a_busy_loop_in_the_caller(self, loop, rec):
        def spin():
            until = time.perf_counter() + STALL_S
            while time.perf_counter() < until:
                pass

        record = _plant(loop, rec, between=spin)
        assert record["outside_spans_ms"] >= 0.9e3 * STALL_S
        # on a CPU nearly the whole interval, but for what a loaded test
        # machine takes from it (which is then run-queue delay)
        assert (record["cpu_ms"] + record.get("run_delay_ms", 0.0)
                >= 0.8e3 * STALL_S)
        assert record["word"] in ("caller_cpu", "runnable_not_run")

    def test_a_collection_over_a_large_graph(self, loop, rec):
        graph = _Graph(600_000)
        t0 = time.time_ns()

        def collect():
            until = time.perf_counter() + STALL_S
            while time.perf_counter() < until:
                gc.collect()

        record = _plant(loop, rec, between=collect)
        del graph
        assert record["word"] == "gc"
        assert record["gc_ms"] >= 0.9e3 * STALL_S
        assert record["parts_ms"]["runtime.gc"] >= 0.8e3 * STALL_S
        pauses = [s for s in _named(rec, "runtime.gc") if s.start_ns >= t0]
        assert any(s.attrs["generation"] == 2 for s in pauses)
        assert all(s.tid == threading.get_ident() for s in pauses)


class TestTheTick:
    def test_a_span_on_steps_20_and_40_and_on_no_other(self, loop, rec):
        start = loop.trainer._digest_steps
        for _ in range(45):
            loop.step()
        ticks = _named(rec, "trainer.step.tick")
        want = [n for n in range(start + 1, start + 46) if n % 20 == 0]
        assert [t.attrs["step"] for t in ticks] == want and len(want) >= 2
        steps = {s.span_id: s for s in _named(rec, "trainer.step")}
        assert len(steps) == 45
        for tick in ticks:
            parent = steps[tick.parent_span_id]        # a child of its step
            assert parent.start_ns <= tick.start_ns <= tick.end_ns <= (
                parent.end_ns)
            took = (tick.end_ns - tick.start_ns) * 1e-9
            parts = [tick.attrs[k] for k in (
                "poll_s", "memscope_s", "digests_s", "write_s")]
            assert all(p >= 0 for p in parts) and sum(parts) <= took
            assert set(step_account.host_pressure()) <= set(tick.attrs)
            assert tick.attrs["cpu_ns"] >= 0
        # every step took its account; none but the ticks' holds a tick
        assert all(ACCOUNT_ATTRS <= set(s.attrs) for s in steps.values())
        # three spans a step and the ticks: what the ring's budget counts
        others = {t.name for t in rec.spans if type(t) is not dict} - {
            "trainer.step", "trainer.step.dispatch", "trainer.shard_batch",
            "trainer.step.tick", "runtime.gc", "mem.sample"}
        assert others == set()
        for name in ("trainer.step.dispatch", "trainer.shard_batch"):
            assert len(_named(rec, name)) == 45

    def test_the_sown_stats_are_read_inside_a_timed_span(
            self, loop, rec, monkeypatch):
        trainer = loop.trainer
        open_at_read = []
        real = jax.device_get

        def watched(x):
            open_at_read.append(trace.current_span().name)
            time.sleep(0.003)
            return real(x)

        monkeypatch.setattr(jax, "device_get", watched)
        monkeypatch.setattr(trainer, "_stats_kept", None)
        sown = {"stats": {"layers": {"mlp": {
            "load_max_over_mean": (jnp.asarray([1.5, 2.5]),),
            "rows_held_over_live": (jnp.asarray([1.25, 1.25]),)}}}}
        jax.block_until_ready(sown)
        first, second = {}, {}
        trainer._note_model_stats(20, sown, first)     # kept, nothing read
        assert first == {} and not _named(rec, "trainer.model_stats")
        trainer._note_model_stats(40, sown, second)
        (stats,) = _named(rec, "trainer.model_stats")
        assert open_at_read == ["trainer.model_stats"] * 2
        assert second["stats_leaves"] == 2
        assert 0.006 <= second["stats_read_s"] <= (
            stats.end_ns - stats.start_ns) * 1e-9
        # the record of the sown values, as it was
        assert stats.attrs["step"] == 20
        assert stats.attrs["load_max_over_mean"] == [1.5, 2.5]
        assert stats.attrs["rows_held_over_live"] == [1.25, 1.25]
        trainer._stats_kept = None
