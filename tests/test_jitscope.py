"""Compile observatory: trigger classification, the watch wrapper,
dispatch stalls, digest plumbing, the master time-series/sentinel/
incident wiring, and the dashboard surface (ISSUE 14)."""

import json
import time
from types import SimpleNamespace

import pytest

from dlrover_tpu.observability import flight_recorder, jitscope
from dlrover_tpu.observability.jitscope import (
    classify_trigger,
    merge_digest,
    signature_of,
)


@pytest.fixture(autouse=True)
def _fresh_scope():
    jitscope.reset_scope(warm_expected=False, cache_enabled=False)
    yield
    jitscope.reset_scope()


def _sig(shapes=((4,),), dtypes=("float32",), specs=("",),
         meshes=(), static=None):
    return {
        "shapes": tuple(shapes), "dtypes": tuple(dtypes),
        "specs": tuple(specs), "meshes": tuple(meshes),
        "static": dict(static or {}),
    }


class TestTriggerClassification:
    def test_cold_site_is_first_trace(self):
        assert classify_trigger(
            None, _sig(), missed=False, cache_enabled=False,
            warm_expected=False,
        ) == "first-trace"

    def test_cold_site_warm_miss_is_cache_miss(self):
        """A warm restart's first call site SHOULD hit the persistent
        cache; a miss there is the cache-cold signature, not a routine
        first trace."""
        assert classify_trigger(
            None, _sig(), missed=True, cache_enabled=True,
            warm_expected=True,
        ) == "persistent-cache-miss"

    def test_cold_boot_miss_stays_first_trace(self):
        # no warmth expected: a miss on the true first boot is normal
        assert classify_trigger(
            None, _sig(), missed=True, cache_enabled=True,
            warm_expected=False,
        ) == "first-trace"

    def test_shape_delta(self):
        assert classify_trigger(
            _sig(shapes=((4,),)), _sig(shapes=((8,),)),
            missed=True, cache_enabled=True, warm_expected=True,
        ) == "arg-shape-delta"

    def test_dtype_delta(self):
        assert classify_trigger(
            _sig(dtypes=("float32",)), _sig(dtypes=("bfloat16",)),
            missed=False, cache_enabled=False, warm_expected=False,
        ) == "dtype-delta"

    def test_sharding_delta(self):
        assert classify_trigger(
            _sig(specs=("PartitionSpec('dp',)",), meshes=("m1",)),
            _sig(specs=("PartitionSpec()",), meshes=("m1",)),
            missed=False, cache_enabled=False, warm_expected=False,
        ) == "sharding-delta"

    def test_mesh_change_outranks_other_deltas(self):
        # an elastic resize changes shapes AND specs AND the mesh: the
        # mesh is the root cause and must win the classification
        assert classify_trigger(
            _sig(shapes=((8,),), specs=("PartitionSpec('dp',)",),
                 meshes=("((dp,4))x4",)),
            _sig(shapes=((4,),), specs=("PartitionSpec('dp',)",),
                 meshes=("((dp,2))x2",)),
            missed=True, cache_enabled=True, warm_expected=True,
        ) == "mesh-change"

    def test_donation_mismatch(self):
        assert classify_trigger(
            _sig(static={"donate": True}), _sig(static={"donate": False}),
            missed=False, cache_enabled=False, warm_expected=False,
        ) == "donation-mismatch"

    def test_identical_signature_miss_is_cache_miss(self):
        assert classify_trigger(
            _sig(), _sig(), missed=True, cache_enabled=True,
            warm_expected=False,
        ) == "persistent-cache-miss"

    def test_identical_signature_no_cache_is_retrace(self):
        assert classify_trigger(
            _sig(), _sig(), missed=False, cache_enabled=False,
            warm_expected=False,
        ) == "retrace"


class TestSignature:
    def test_leaves_and_statics(self):
        import jax.numpy as jnp

        sig = signature_of(
            (jnp.ones((2, 3)), {"k": jnp.ones(4, jnp.int32)}), {},
            static={"donate": True},
        )
        assert (2, 3) in sig["shapes"] and (4,) in sig["shapes"]
        assert "float32" in sig["dtypes"] and "int32" in sig["dtypes"]
        assert sig["static"] == {"donate": True}

    def test_non_array_leaves_tolerated(self):
        sig = signature_of((3, "x"), {})
        assert len(sig["shapes"]) == 2

    def test_mesh_fingerprint_distinguishes_layouts(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        devices = jax.devices()[:4]
        mesh_dp = Mesh(np.array(devices).reshape(4), ("dp",))
        mesh_2d = Mesh(np.array(devices).reshape(2, 2), ("dp", "fsdp"))
        x = jax.device_put(
            np.ones((4, 4), np.float32),
            NamedSharding(mesh_dp, PartitionSpec("dp")),
        )
        y = jax.device_put(
            np.ones((4, 4), np.float32),
            NamedSharding(mesh_2d, PartitionSpec("dp")),
        )
        sig_x = signature_of((x,), {})
        sig_y = signature_of((y,), {})
        assert sig_x["meshes"] != sig_y["meshes"]
        assert classify_trigger(
            sig_x, sig_y, missed=False, cache_enabled=False,
            warm_expected=False,
        ) == "mesh-change"

    def test_sharding_delta_same_mesh(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("dp",))
        x = jax.device_put(
            np.ones((4, 4), np.float32),
            NamedSharding(mesh, PartitionSpec("dp")),
        )
        y = jax.device_put(
            np.ones((4, 4), np.float32),
            NamedSharding(mesh, PartitionSpec(None, "dp")),
        )
        assert classify_trigger(
            signature_of((x,), {}), signature_of((y,), {}),
            missed=False, cache_enabled=False, warm_expected=False,
        ) == "sharding-delta"


class TestWatch:
    def test_first_trace_then_silent_cached_path(self):
        import jax
        import jax.numpy as jnp

        fn = jitscope.watch(jax.jit(lambda v: v + 1.0), "t.first")
        fn(jnp.ones(8))
        event = fn.last_event
        assert event is not None
        assert event["trigger"] == "first-trace"
        assert event["compile_s"] > 0
        assert event["compile_s"] <= event["dispatch_s"]
        fn(jnp.ones(8))
        assert fn.last_event is None
        assert jitscope.scope().summary()["events"] == 1

    def test_shape_and_dtype_deltas_recorded(self):
        import jax
        import jax.numpy as jnp

        fn = jitscope.watch(jax.jit(lambda v: v * 2.0), "t.delta")
        fn(jnp.ones(8))
        fn(jnp.ones(16))
        assert fn.last_event["trigger"] == "arg-shape-delta"
        fn(jnp.ones(16, jnp.bfloat16))
        assert fn.last_event["trigger"] == "dtype-delta"
        by_trigger = jitscope.scope().summary()["by_trigger"]
        assert by_trigger["arg-shape-delta"] == 1
        assert by_trigger["dtype-delta"] == 1

    def test_donation_mismatch_across_watches_of_one_site(self):
        import jax
        import jax.numpy as jnp

        fn_a = jitscope.watch(
            jax.jit(lambda v: v - 1.0), "t.donate",
            static={"donate": True},
        )
        fn_a(jnp.ones(8))
        fn_b = jitscope.watch(
            jax.jit(lambda v: v - 1.0), "t.donate",
            static={"donate": False},
        )
        fn_b(jnp.ones(8))
        assert fn_b.last_event["trigger"] == "donation-mismatch"

    def test_kill_switch_bypasses_everything(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        monkeypatch.setenv("DLROVER_TPU_JITSCOPE", "0")
        fn = jitscope.watch(jax.jit(lambda v: v / 2.0), "t.off")
        out = fn(jnp.ones(8))
        assert out is not None
        assert fn.last_event is None
        assert jitscope.scope().summary()["events"] == 0

    def test_compile_event_span_lands_in_recorder(self):
        import jax
        import jax.numpy as jnp

        flight_recorder.recorder().reset()
        fn = jitscope.watch(jax.jit(lambda v: v * 3.0), "t.span")
        fn(jnp.ones(8))
        spans = flight_recorder.recorder().snapshot(stacks=False)[
            "spans"
        ]
        mine = [
            s for s in spans
            if s.get("name") == "jitscope.compile"
            and (s.get("attrs") or {}).get("fn") == "t.span"
        ]
        assert mine
        assert mine[-1]["attrs"]["trigger"] == "first-trace"

    def test_broken_scope_never_breaks_dispatch(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        def boom(*a, **kw):
            raise RuntimeError("scope broken")

        monkeypatch.setattr(jitscope.JitScope, "record_compile", boom)
        fn = jitscope.watch(jax.jit(lambda v: v + 5.0), "t.broken")
        out = fn(jnp.ones(8))
        assert float(out[0]) == 6.0


class TestDispatchStall:
    def test_stall_span_and_counter(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        monkeypatch.setenv("DLROVER_TPU_JITSCOPE_STALL_MS", "1")
        flight_recorder.recorder().reset()
        fn = jitscope.watch(
            jax.jit(lambda v: (v @ v.T).sum()), "t.stall"
        )
        fn(jnp.ones((64, 64)))
        assert jitscope.scope().digest()["js_stalls"] == 1.0
        spans = flight_recorder.recorder().snapshot(stacks=False)[
            "spans"
        ]
        stalls = [
            s for s in spans
            if s.get("name") == "jitscope.dispatch_stall"
        ]
        assert stalls
        assert stalls[-1]["attrs"]["fn"] == "t.stall"
        assert stalls[-1]["attrs"]["blocked_s"] > 0

    def test_no_stall_below_threshold(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        monkeypatch.setenv("DLROVER_TPU_JITSCOPE_STALL_MS", "60000")
        fn = jitscope.watch(jax.jit(lambda v: v + 7.0), "t.fast")
        fn(jnp.ones(8))
        assert jitscope.scope().digest()["js_stalls"] == 0.0

    def test_inflight_registry_snapshot(self):
        assert jitscope.inflight() == []


class TestDigest:
    def test_digest_keys_and_merge_rules(self):
        rank0 = {
            "js_ts": 100.0, "js_seq": 2.0, "js_compile_s": 1.5,
            "js_hits": 1.0, "js_misses": 1.0, "js_stalls": 0.0,
            "js_warm": 0.0, "js_cache": 1.0,
        }
        rank1 = {
            "js_ts": 90.0, "js_seq": 1.0, "js_compile_s": 0.5,
            "js_hits": 0.0, "js_misses": 1.0, "js_stalls": 2.0,
            "js_warm": 1.0, "js_cache": 1.0,
        }
        merged = {}
        merge_digest(merged, rank0)
        merge_digest(merged, rank1)
        assert merged["js_ts"] == 100.0          # newest event
        assert merged["js_seq"] == 3.0           # node total
        assert merged["js_compile_s"] == 2.0
        assert merged["js_hits"] == 1.0
        assert merged["js_misses"] == 2.0
        assert merged["js_stalls"] == 2.0
        assert merged["js_warm"] == 1.0          # any warm rank
        assert merged["js_cache"] == 1.0

    def test_merge_ignores_foreign_keys(self):
        merged = {}
        merge_digest(merged, {"gp_wall": 5.0, "step_p50_s": 0.1})
        assert merged == {}

    def test_agent_collector_merges_js_keys(self, monkeypatch, tmp_path):
        """The real collector path: two rank files' compile counters
        SUM into node totals on the heartbeat digest."""
        from dlrover_tpu.agent.elastic_agent import (
            ElasticAgent,
            ElasticLaunchConfig,
        )

        base = tmp_path / "runtime_metrics.json"
        monkeypatch.setenv(
            "DLROVER_TPU_RUNTIME_METRICS_PATH", str(base)
        )
        now = time.time()
        for rank, compile_s in enumerate([1.0, 3.0]):
            with open(f"{base}.rank{rank}", "w") as f:
                json.dump({
                    "ts": now, "step_p50_s": 0.1,
                    "js_ts": now, "js_seq": 1.0,
                    "js_compile_s": compile_s, "js_hits": 1.0,
                    "js_misses": 0.0, "js_stalls": 0.0,
                    "js_warm": 1.0, "js_cache": 1.0,
                }, f)

        class _Client:
            node_id = 0

        agent = ElasticAgent(_Client(), ElasticLaunchConfig())
        digest = agent._collect_digest()  # noqa: SLF001 - the real path
        assert digest["js_compile_s"] == 4.0
        assert digest["js_hits"] == 2.0
        assert digest["js_warm"] == 1.0


def _js_digest(ts, seq, compile_s, hits, misses, warm=1.0, cache=1.0,
               stalls=0.0, boot=100.0):
    return {
        "js_ts": ts, "js_boot": boot, "js_seq": seq,
        "js_compile_s": compile_s,
        "js_hits": hits, "js_misses": misses, "js_stalls": stalls,
        "js_warm": warm, "js_cache": cache,
    }


class TestTimeSeriesCompile:
    def _store(self):
        from dlrover_tpu.master.timeseries import TimeSeriesStore

        return TimeSeriesStore()

    def test_seq_advance_plots_window_deltas(self):
        store = self._store()
        base = time.time() - 60
        store.record_digest(
            0, _js_digest(base, 1.0, 0.5, 0.0, 1.0), ts=base
        )
        assert store.series("node0.compile.s", res=1.0) == []
        store.record_digest(
            0, _js_digest(base + 20, 3.0, 4.5, 1.0, 2.0), ts=base + 20
        )
        series = store.series("node0.compile.s", res=1.0)
        assert len(series) == 1
        assert series[0]["mean"] == pytest.approx(4.0)
        ratio = store.series("node0.compile.hit_ratio", res=1.0)
        assert ratio[0]["mean"] == pytest.approx(0.5)

    def test_heartbeat_without_advance_plots_nothing(self):
        store = self._store()
        base = time.time() - 60
        digest = _js_digest(base, 2.0, 1.0, 1.0, 1.0)
        store.record_digest(0, digest, ts=base)
        store.record_digest(0, digest, ts=base + 15)
        store.record_digest(0, digest, ts=base + 30)
        assert store.series("node0.compile.s", res=1.0) == []

    def test_restart_plots_fresh_boot_burst(self):
        """A restarted process's counters reset; its first digest's
        cumulative account IS that boot's compile bill — exactly the
        cost an elastic restart pays, plotted whole."""
        store = self._store()
        base = time.time() - 60
        store.record_digest(
            0, _js_digest(base, 5.0, 9.0, 4.0, 1.0), ts=base
        )
        # restart: new boot marker, seq dropped, small cumulative
        store.record_digest(
            0, _js_digest(base + 30, 1.0, 0.7, 1.0, 0.0, boot=200.0),
            ts=base + 30,
        )
        series = store.series("node0.compile.s", res=1.0)
        assert len(series) == 1
        assert series[0]["mean"] == pytest.approx(0.7)
        nodes = store.compile_nodes()
        assert nodes[0]["hit_ratio"] == pytest.approx(1.0)

    def test_restart_with_larger_seq_still_plots_cumulative(self):
        """The boot marker, not the sequence, decides: a restarted
        boot whose event count EXCEEDS the dead boot's must not be
        differentiated across two unrelated boots (cross-boot deltas
        were the gp_seq/mm_ts bug class)."""
        store = self._store()
        base = time.time() - 60
        store.record_digest(
            0, _js_digest(base, 8.0, 30.0, 8.0, 0.0), ts=base
        )
        # restart: MORE events than the dead boot (9 > 8), all misses
        store.record_digest(
            0, _js_digest(base + 30, 9.0, 40.0, 0.0, 9.0, boot=200.0),
            ts=base + 30,
        )
        series = store.series("node0.compile.s", res=1.0)
        assert series[-1]["last"] == pytest.approx(40.0)  # not 10.0
        nodes = store.compile_nodes()
        assert nodes[0]["window"]["misses"] == pytest.approx(9.0)
        assert nodes[0]["window_hit_ratio"] == pytest.approx(0.0)

    def test_job_rollups_worst_node(self):
        store = self._store()
        base = time.time() - 60
        for node, (c0, c1, hits) in enumerate(
            [(0.5, 1.0, 1.0), (0.5, 6.5, 0.0)]
        ):
            store.record_digest(
                node, _js_digest(base, 1.0, c0, 0.0, 1.0), ts=base
            )
            store.record_digest(
                node,
                _js_digest(base + 20, 2.0, c1, hits, 2.0),
                ts=base + 20,
            )
        job = store.series("job.compile.s", res=1.0)
        # both nodes' windows landed in the bucket; node1's 6.0s is
        # the max and the last point
        assert job[-1]["last"] == pytest.approx(6.0)
        assert job[-1]["max"] == pytest.approx(6.0)
        ratio = store.series("job.compile.hit_ratio", res=1.0)
        assert ratio[-1]["last"] == pytest.approx(0.0)

    def test_job_series_never_rerecords_stale_windows(self):
        """Each node's differentiated window joins job.compile.s
        exactly once: node B advancing later must not re-add node A's
        big window into a second bucket (a single finished compile
        double-counted could fabricate a recompile storm)."""
        store = self._store()
        base = time.time() - 120
        store.record_digest(
            0, _js_digest(base, 1.0, 0.0, 0.0, 0.0), ts=base
        )
        store.record_digest(  # node A: one 60s compile window
            0, _js_digest(base + 10, 2.0, 60.0, 0.0, 1.0), ts=base + 10
        )
        store.record_digest(
            1, _js_digest(base + 30, 1.0, 0.0, 0.0, 0.0), ts=base + 30
        )
        store.record_digest(  # node B advances 20s later, tiny window
            1, _js_digest(base + 40, 2.0, 0.5, 1.0, 0.0), ts=base + 40
        )
        points = store.series("job.compile.s", res=1.0)
        sixties = [p for p in points if p["max"] >= 59.0]
        assert len(sixties) == 1  # A's compile counted ONCE
        assert points[-1]["last"] == pytest.approx(0.5)

    def test_eventless_heartbeat_keeps_last_window_snapshot(self):
        """A heartbeat re-shipping the same account must not strip the
        latest view's window (the cache-cold sentinel's windowed-ratio
        input) — the re-ship scenario that used to re-expose the
        cumulative fallback."""
        store = self._store()
        base = time.time() - 60
        store.record_digest(
            0, _js_digest(base, 1.0, 2.0, 0.0, 1.0), ts=base
        )
        advance = _js_digest(base + 10, 3.0, 4.0, 2.0, 1.0)
        store.record_digest(0, advance, ts=base + 10)
        assert store.compile_nodes()[0]["window"] is not None
        store.record_digest(0, advance, ts=base + 25)  # re-ship
        entry = store.compile_nodes()[0]
        assert entry["window"] is not None
        assert entry["window_hit_ratio"] == pytest.approx(1.0)
        assert entry["ts"] == pytest.approx(base + 10)

    def test_job_hit_ratio_is_windowed_not_cumulative(self):
        """A long healthy run must not dilute a fresh cold streak: the
        job rollup uses the WINDOW's hits/misses, so 4 historic hits
        followed by 2 fresh misses reads 0.0, not 4/6."""
        store = self._store()
        base = time.time() - 60
        store.record_digest(
            0, _js_digest(base, 4.0, 1.0, 4.0, 0.0), ts=base
        )
        store.record_digest(
            0, _js_digest(base + 20, 6.0, 3.0, 4.0, 2.0), ts=base + 20
        )
        ratio = store.series("job.compile.hit_ratio", res=1.0)
        assert ratio[-1]["last"] == pytest.approx(0.0)
        # the latest view still carries BOTH flavors
        nodes = store.compile_nodes()
        assert nodes[0]["hit_ratio"] == pytest.approx(4.0 / 6.0)
        assert nodes[0]["window_hit_ratio"] == pytest.approx(0.0)

    def test_evict_clears_compile_state(self):
        store = self._store()
        base = time.time() - 60
        store.record_digest(
            0, _js_digest(base, 1.0, 0.5, 1.0, 0.0), ts=base
        )
        assert 0 in store.compile_nodes()
        store.evict_node(0)
        assert 0 not in store.compile_nodes()

    def test_no_js_keys_is_inert(self):
        store = self._store()
        store.record_digest(0, {"step_p50_s": 0.2})
        assert store.compile_nodes() == {}


class TestCompileSentinel:
    def _setup(self, store=None):
        from dlrover_tpu.master.timeseries import TimeSeriesStore
        from dlrover_tpu.observability.sentinel import CompileSentinel

        store = store or TimeSeriesStore()
        return store, CompileSentinel(store)

    def test_cache_cold_fires_on_warm_miss(self):
        store, sentinel = self._setup()
        now = time.time()
        store.record_digest(
            0, _js_digest(now, 1.0, 2.0, 0.0, 1.0, warm=1.0), ts=now
        )
        obs = sentinel.observe()
        assert obs.observed
        assert obs.extra["kind"] == "cache_cold"
        assert obs.extra["culprit"] == 0
        assert obs.extra["phase"] == "compile"
        assert sentinel.incident_kind == "cache_cold"

    def test_cache_cold_dedups_same_sample(self):
        store, sentinel = self._setup()
        now = time.time()
        store.record_digest(
            0, _js_digest(now, 1.0, 2.0, 0.0, 1.0, warm=1.0), ts=now
        )
        assert sentinel.observe().observed
        assert not sentinel.observe().observed  # same sample ts
        # a NEW sample still below the floor re-reports
        store.record_digest(
            0, _js_digest(now + 10, 2.0, 4.0, 0.0, 2.0, warm=1.0),
            ts=now + 10,
        )
        assert sentinel.observe().observed

    def test_quiet_when_warm_not_expected_or_cache_off(self):
        store, sentinel = self._setup()
        now = time.time()
        store.record_digest(
            0, _js_digest(now, 1.0, 2.0, 0.0, 1.0, warm=0.0), ts=now
        )
        store.record_digest(
            1, _js_digest(now, 1.0, 2.0, 0.0, 1.0, warm=1.0,
                          cache=0.0), ts=now
        )
        assert not sentinel.observe().observed

    def test_mid_run_wipe_fires_despite_diluted_cumulative(self):
        """A long warm run then a wiped cache: the cumulative ratio is
        still high (20 hits vs 3 misses) but the WINDOW is all misses
        — the sentinel must read the windowed ratio and fire."""
        store, sentinel = self._setup()
        now = time.time()
        store.record_digest(
            0, _js_digest(now - 20, 20.0, 5.0, 20.0, 0.0, warm=1.0),
            ts=now - 20,
        )
        assert not sentinel.observe().observed  # healthy
        store.record_digest(
            0, _js_digest(now, 23.0, 11.0, 20.0, 3.0, warm=1.0),
            ts=now,
        )
        obs = sentinel.observe()
        assert obs.observed
        assert obs.extra["kind"] == "cache_cold"
        assert obs.extra["hit_ratio"] == pytest.approx(0.0)

    def test_recovered_cache_not_refired_by_heartbeat_reship(self):
        """Boot misses fire once; the cache then recovers (all-hit
        window).  A later eventless heartbeat re-shipping that account
        must NOT re-open cache_cold from the still-diluted cumulative
        ratio."""
        store, sentinel = self._setup()
        now = time.time()
        store.record_digest(  # boot: all misses -> fires
            0, _js_digest(now - 40, 3.0, 6.0, 0.0, 3.0, warm=1.0),
            ts=now - 40,
        )
        assert sentinel.observe().extra["kind"] == "cache_cold"
        store.record_digest(  # recovery: all-hit window
            0, _js_digest(now - 20, 6.0, 6.5, 3.0, 3.0, warm=1.0),
            ts=now - 20,
        )
        assert not sentinel.observe().observed
        store.record_digest(  # eventless heartbeat re-ship
            0, _js_digest(now - 20, 6.0, 6.5, 3.0, 3.0, warm=1.0),
            ts=now,
        )
        assert not sentinel.observe().observed

    def test_quiet_above_ratio_floor(self):
        store, sentinel = self._setup()
        now = time.time()
        # 3 hits 1 miss = 0.75 >= the 0.5 floor
        store.record_digest(
            0, _js_digest(now, 1.0, 2.0, 3.0, 1.0, warm=1.0), ts=now
        )
        assert not sentinel.observe().observed

    def test_storm_fires_after_baseline(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_SENTINEL_CONSECUTIVE", "2")
        store, sentinel = self._setup()
        base = time.time() - 400
        for i in range(14):
            store.add(
                "job.compile.s", 0.2 if i < 10 else 30.0,
                base + i * 10,
            )
        obs = sentinel.observe()
        assert obs.observed
        assert obs.extra["kind"] == "recompile_storm"
        assert sentinel.incident_kind == "recompile_storm"

    def test_storm_abs_floor_suppresses_noise(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_SENTINEL_CONSECUTIVE", "2")
        store, sentinel = self._setup()
        base = time.time() - 400
        # jitter between 0.1 and 0.4s/window: under the 5s abs floor
        for i in range(14):
            store.add(
                "job.compile.s", 0.1 if i % 2 else 0.4, base + i * 10
            )
        assert not sentinel.observe().observed

    def test_cold_outranks_storm(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_SENTINEL_CONSECUTIVE", "2")
        store, sentinel = self._setup()
        now = time.time()
        base = now - 400
        for i in range(14):
            store.add(
                "job.compile.s", 0.2 if i < 10 else 30.0,
                base + i * 10,
            )
        store.record_digest(
            0, _js_digest(now, 1.0, 2.0, 0.0, 1.0, warm=1.0), ts=now
        )
        obs = sentinel.observe()
        assert obs.extra["kind"] == "cache_cold"

    def test_registered_in_standard_set(self):
        from dlrover_tpu.master.timeseries import TimeSeriesStore
        from dlrover_tpu.observability.sentinel import (
            CompileSentinel,
            register_sentinels,
        )

        class _Diag:
            def __init__(self):
                self.registered = []

            def register(self, d):
                self.registered.append(d)

        diag = _Diag()
        sentinels = register_sentinels(diag, TimeSeriesStore())
        assert any(
            isinstance(s, CompileSentinel) for s in sentinels
        )


class TestIncidentClassification:
    def test_chaos_point_maps_to_compile_phase(self):
        from dlrover_tpu.observability.incidents import classify

        verdict = classify(chaos_records=[
            {"type": "CHAOS", "point": "jitscope.compile",
             "kind": "delay", "span_id": "ab"},
        ])
        assert verdict["phase"] == "compile"

    def test_stuck_compile_span_maps_to_compile_phase(self):
        from dlrover_tpu.observability.incidents import classify

        verdict = classify(dumps={
            "node_0": {"open_spans": [
                {"name": "jitscope.compile", "open_for_s": 12.0},
            ]},
        })
        assert verdict["phase"] == "compile"
        assert verdict["stuck_op"] == "jitscope.compile"

    def test_finalize_embeds_compile_events(self, monkeypatch, tmp_path):
        from dlrover_tpu.observability.incidents import IncidentManager

        monkeypatch.setenv(
            "DLROVER_TPU_INCIDENT_DIR", str(tmp_path / "inc")
        )
        monkeypatch.setenv("DLROVER_TPU_INCIDENT_COOLDOWN_S", "0")
        monkeypatch.setenv("DLROVER_TPU_INCIDENT_GRACE_S", "0")
        flight_recorder.recorder().reset()
        sc = jitscope.reset_scope(
            warm_expected=True, cache_enabled=True
        )
        sc.record_compile(
            "train_step", _sig(), compile_s=4.2, hits=0, misses=1,
            start_ts=time.time() - 5, end_ts=time.time() - 1,
            wall_s=4.0,
        )
        manager = IncidentManager()
        incident_id = manager.open(
            "cache_cold", detail="drill", culprit=0,
            phase_hint="compile", broadcast=False,
        )
        incident = manager.finalize(incident_id, force=True)
        compile_evidence = incident.get("compile") or {}
        assert compile_evidence.get("events")
        last_miss = compile_evidence.get("last_miss") or {}
        assert last_miss.get("fn") == "train_step"
        assert last_miss.get("trigger") == "persistent-cache-miss"

    def test_non_compile_incident_has_no_compile_key(
        self, monkeypatch, tmp_path
    ):
        from dlrover_tpu.observability.incidents import IncidentManager

        monkeypatch.setenv(
            "DLROVER_TPU_INCIDENT_DIR", str(tmp_path / "inc")
        )
        monkeypatch.setenv("DLROVER_TPU_INCIDENT_COOLDOWN_S", "0")
        flight_recorder.recorder().reset()
        manager = IncidentManager()
        incident_id = manager.open(
            "kv_fault", detail="x", culprit=1, phase_hint="kv",
            broadcast=False,
        )
        incident = manager.finalize(incident_id, force=True)
        assert "compile" not in incident


class TestDashboardCompile:
    def test_compile_endpoint_over_http(self):
        import urllib.request

        from dlrover_tpu.master.dashboard import DashboardServer
        from dlrover_tpu.master.timeseries import TimeSeriesStore

        store = TimeSeriesStore()
        base = time.time() - 30
        store.record_digest(
            0, _js_digest(base, 1.0, 0.5, 0.0, 1.0), ts=base
        )
        store.record_digest(
            0, _js_digest(base + 10, 2.0, 2.5, 1.0, 2.0),
            ts=base + 10,
        )
        master = SimpleNamespace(
            servicer=SimpleNamespace(timeseries=store),
        )
        server = DashboardServer(master, port=0)
        server.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/compile", timeout=5
            ) as resp:
                body = json.loads(resp.read().decode())
            node = body["nodes"]["0"]
            assert node["compile_s"] == 2.5
            assert node["warm_expected"] is True
            assert body["job"]["s"] == pytest.approx(2.0)
        finally:
            server.stop()


class TestTrainerIntegration:
    def test_trainer_step_watched_and_goodput_split(
        self, monkeypatch, tmp_path
    ):
        """The real trainer path: the jit step is a watched call site,
        the first dispatch records a classified event, the goodput
        ledger charges measured compile + execution remainder, and the
        rank digest file carries the js_ keys."""
        import jax
        import jax.numpy as jnp
        import optax
        import flax.linen as nn

        from dlrover_tpu.observability import goodput, jitscope
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.trainer.train import Trainer

        monkeypatch.setenv("DLROVER_TPU_GOODPUT_RES_S", "0.05")
        monkeypatch.setenv("DLROVER_TPU_DIGEST_EVERY", "2")
        monkeypatch.setenv(
            "DLROVER_TPU_RUNTIME_METRICS_PATH",
            str(tmp_path / "rt"),
        )

        class MLP(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(16)(
                    nn.Dense(32)(jax.nn.one_hot(x, 16))
                )

        goodput.reset_ledger()
        jitscope.reset_scope(warm_expected=False, cache_enabled=False)
        mesh = build_mesh(MeshConfig(dp=8))
        trainer = Trainer(MLP(), optax.adamw(1e-3), mesh)
        state = trainer.create_state(
            jax.random.PRNGKey(0), jnp.ones((8, 4), jnp.int32)
        )
        batch = {
            "input_ids": jnp.ones((8, 4), jnp.int32),
            "labels": jnp.ones((8, 4), jnp.int32),
        }
        for _ in range(4):
            state, _ = trainer.train_step(state, batch)
        assert isinstance(
            trainer._jit_step, jitscope.WatchedFunction
        )
        events = jitscope.scope().events()
        assert events and events[-1]["fn"] == "trainer.train_step"
        assert events[-1]["trigger"] == "first-trace"
        phases = goodput.ledger().summary()["phases"]
        assert phases["compile"] > 0
        rank_file = tmp_path / "rt.rank0"
        digest = json.loads(rank_file.read_text())
        assert digest["js_seq"] >= 1.0
        assert digest["js_compile_s"] > 0
        goodput.reset_ledger()
