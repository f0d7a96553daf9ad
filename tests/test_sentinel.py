"""Perf-regression sentinel: EWMA+MAD detector semantics, the series
diagnosticians end-to-end (store -> detector -> DiagnosisManager ->
incident)."""

import time

import pytest

from dlrover_tpu.master.timeseries import TimeSeriesStore
from dlrover_tpu.observability.sentinel import (
    EwmaMadDetector,
    ExposedCommDiagnostician,
    GoodputRegressionDiagnostician,
    StepTimeRegressionDiagnostician,
    register_sentinels,
)


def _det(**kw):
    kw.setdefault("alpha", 0.25)
    kw.setdefault("k", 4.0)
    kw.setdefault("min_samples", 4)
    kw.setdefault("consecutive", 1)
    return EwmaMadDetector(**kw)


class TestDetector:
    def test_stable_series_never_fires(self):
        det = _det(direction="up")
        assert all(
            det.update(0.05 + 0.0005 * (i % 3)) is None
            for i in range(50)
        )

    def test_up_breach_fires(self):
        det = _det(direction="up")
        for _ in range(10):
            det.update(0.05)
        breach = det.update(0.5)
        assert breach is not None
        assert breach["baseline"] == pytest.approx(0.05)
        assert breach["direction"] == "up"

    def test_down_breach_fires_only_downward(self):
        det = _det(direction="down")
        for _ in range(10):
            det.update(0.9)
        assert det.update(5.0) is None  # improvement, not regression
        assert det.update(0.2) is not None

    def test_cold_detector_never_fires(self):
        det = _det(min_samples=8)
        det.update(0.05)
        assert det.update(100.0) is None  # warm-up absorbs it

    def test_consecutive_requirement(self):
        det = _det(consecutive=3)
        for _ in range(10):
            det.update(1.0)
        assert det.update(5.0) is None
        assert det.update(5.0) is None
        breach = det.update(5.0)
        assert breach is not None
        assert breach["streak"] == 3

    def test_streak_resets_on_healthy_sample(self):
        det = _det(consecutive=2)
        for _ in range(10):
            det.update(1.0)
        assert det.update(5.0) is None
        assert det.update(1.0) is None  # streak broken
        assert det.update(5.0) is None  # streak restarts at 1

    def test_fire_rebaselines_to_new_regime(self):
        det = _det()
        for _ in range(10):
            det.update(1.0)
        assert det.update(5.0) is not None
        # the new level is the baseline now: staying there is quiet,
        # a FURTHER regression fires again after re-warm-up
        for _ in range(10):
            det.update(5.0)
        assert det.update(25.0) is not None

    def test_rel_floor_guards_flat_baselines(self):
        det = _det(rel_floor=0.10)
        for _ in range(20):
            det.update(1.0)  # mad collapses to ~0
        assert det.update(1.05) is None  # within the relative floor
        assert det.update(1.2) is not None

    def test_knob_defaults_read_registry(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_SENTINEL_MAD_K", "9.0")
        monkeypatch.setenv("DLROVER_TPU_SENTINEL_CONSECUTIVE", "5")
        det = EwmaMadDetector()
        assert det.k == 9.0
        assert det.consecutive == 5

    def test_bad_direction_rejected(self):
        with pytest.raises(ValueError):
            EwmaMadDetector(direction="sideways")

    def test_abs_floor_guards_zero_baseline(self):
        """A share series that sat at 0.0 through warm-up has baseline
        AND mad 0 — without an absolute floor, the first routine
        nonzero sample (a normal checkpoint's share) is a breach."""
        det = _det(abs_floor=0.10)
        for _ in range(10):
            det.update(0.0)
        assert det.update(0.05) is None  # routine ckpt share
        assert det.update(0.5) is not None  # a real stall still fires

    def test_share_diagnosticians_carry_abs_floor(self):
        from dlrover_tpu.master.timeseries import TimeSeriesStore
        from dlrover_tpu.observability.sentinel import (
            CkptShareDiagnostician,
        )

        store = TimeSeriesStore()
        # zero through warm-up, then a small routine checkpoint share
        _feed(store, "job.share.ckpt_stall", [0.0] * 10 + [0.05, 0.0])
        diag = CkptShareDiagnostician(store, res_s=1.0)
        diag._detector.min_samples = 4
        diag._detector.consecutive = 1
        assert not diag.observe().observed
        assert ExposedCommDiagnostician.abs_floor > 0


def _feed(store, name, values, t0=None, spacing=1.0):
    t0 = t0 if t0 is not None else time.time() - len(values) * spacing - 2
    for i, value in enumerate(values):
        store.add(name, value, ts=t0 + i * spacing)
    return t0


class TestSeriesDiagnosticians:
    def _mk(self, cls, store, **kw):
        diag = cls(store, res_s=1.0)
        diag._detector = _det(direction=cls.direction, **kw)
        return diag

    def test_goodput_drop_fires_and_names_series(self):
        store = TimeSeriesStore()
        _feed(store, "job.goodput", [0.95] * 8 + [0.1, 0.1, 0.95])
        diag = self._mk(GoodputRegressionDiagnostician, store)
        obs = diag.observe()
        assert obs.observed
        assert "job.goodput" in obs.detail
        assert obs.extra["breach"]["direction"] == "down"

    def test_live_bucket_excluded_and_no_refire(self):
        store = TimeSeriesStore()
        now = time.time()
        _feed(store, "job.goodput", [0.95] * 8, t0=now - 10)
        store.add("job.goodput", 0.05, ts=now)  # LIVE bucket
        diag = self._mk(GoodputRegressionDiagnostician, store)
        assert not diag.observe().observed  # dip not yet completed
        store.add("job.goodput", 0.05, ts=now + 1)  # completes it
        assert diag.observe().observed
        # same data again: buckets already consumed
        assert not diag.observe().observed

    def test_step_time_rise_fires_up(self):
        store = TimeSeriesStore()
        _feed(store, "job.step_p50_s", [0.05] * 8 + [0.4, 0.4, 0.05])
        diag = self._mk(StepTimeRegressionDiagnostician, store)
        obs = diag.observe()
        assert obs.observed
        assert "rose" in obs.detail

    def test_exposed_comm_hint_is_collective(self):
        store = TimeSeriesStore()
        _feed(store, "job.share.exposed_comm",
              [0.02] * 8 + [0.5, 0.5, 0.02])
        diag = self._mk(ExposedCommDiagnostician, store)
        obs = diag.observe()
        assert obs.observed
        assert obs.extra["phase"] == "collective"

    def test_empty_series_is_quiet(self):
        diag = GoodputRegressionDiagnostician(TimeSeriesStore())
        assert not diag.observe().observed

    def test_breach_counter_recorded(self):
        from dlrover_tpu.observability import metrics as obs_metrics

        store = TimeSeriesStore()
        _feed(store, "job.goodput", [0.95] * 8 + [0.1, 0.1, 0.95])
        diag = self._mk(GoodputRegressionDiagnostician, store)
        before = obs_metrics.registry().counter_value(
            "dlrover_tpu_sentinel_breaches_total",
            series="job.goodput", detector="goodput_regression",
        )
        assert diag.observe().observed
        after = obs_metrics.registry().counter_value(
            "dlrover_tpu_sentinel_breaches_total",
            series="job.goodput", detector="goodput_regression",
        )
        assert after == before + 1

    def test_manager_opens_classified_incident(self, tmp_path,
                                               monkeypatch):
        from dlrover_tpu.diagnosis.diagnostician import DiagnosisManager
        from dlrover_tpu.observability import flight_recorder
        from dlrover_tpu.observability.incidents import IncidentManager

        monkeypatch.setenv("DLROVER_TPU_INCIDENT_DIR",
                           str(tmp_path / "incidents"))
        monkeypatch.setenv("DLROVER_TPU_INCIDENT_COOLDOWN_S", "0")
        flight_recorder.recorder().reset()
        store = TimeSeriesStore()
        _feed(store, "job.goodput", [0.95] * 8 + [0.1, 0.1, 0.95])
        manager = DiagnosisManager()
        diag = self._mk(GoodputRegressionDiagnostician, store)
        manager.register(diag)
        incident_manager = IncidentManager()
        incident_manager.set_timeseries(store)
        manager.set_incident_manager(incident_manager)
        actions = manager.diagnose_once()
        assert [a.action_type for a in actions] == ["event"]
        incidents = incident_manager.list_incidents()
        assert len(incidents) == 1
        assert incidents[0]["kind"] == "goodput_regression"
        incident = incident_manager.finalize(
            incidents[0]["incident_id"], force=True
        )
        # the incident timeline carries the goodput curve the breach
        # landed on
        assert incident["timeline"]["counters"] > 0

    def test_register_sentinels_attaches_standard_set(self):
        from dlrover_tpu.diagnosis.diagnostician import DiagnosisManager

        manager = DiagnosisManager()
        sentinels = register_sentinels(manager, TimeSeriesStore())
        assert {
            s.series for s in sentinels if getattr(s, "series", "")
        } == {
            "job.goodput", "job.step_p50_s", "job.share.exposed_comm",
            "job.share.ckpt_stall",
            # r25: the data-pipeline pair
            "job.share.input_starved", "job.data.lease_p99_ms",
        }
        # r16: the dynamic-series slow-link sentinel rides along
        assert any(s.name == "slow_link" for s in sentinels)
        # all quiet on an empty store
        assert manager.diagnose_once() == []
