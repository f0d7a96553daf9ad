"""bench.py trajectory recording: BENCH_history.jsonl entries and the
bench-side regression gate (ISSUE 10 satellite)."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "bench_under_test", os.path.join(REPO, "bench.py")
)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def _result(value=0.5, step_ms=100.0, tokens=1000, tpu_down=False):
    detail = {
        "step_ms": step_ms,
        "tokens_per_sec": tokens,
        "mfu": 0.3,
        "flight_recorder": {"pct_of_step": 0.05, "append_us": 1.2},
        "goodput_ledger": {
            "goodput": 0.91, "dominant": "compute",
            "phases": {"compute": 9.1, "idle_unknown": 0.9},
        },
        "goodput": {"training_goodput": 0.95, "goodput": 0.7},
    }
    if tpu_down:
        detail["tpu_unavailable"] = True
        detail["tpu_probe"] = {
            "ok": False, "attempts": 4, "last_error": "rc=1: wedged"
        }
    return {
        "metric": "flash_ckpt_blocking_save_s (x, 1 host)",
        "value": value, "unit": "s", "vs_baseline": 2.0,
        "detail": detail,
    }


class TestHistoryEntry:
    def test_entry_carries_the_acceptance_fields(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_BENCH_TIER1_DOTS", "902")
        entry = bench._history_entry(_result(), preset="default")
        assert entry["tier1_dots"] == 902
        assert entry["blocking_save_s"] == 0.5  # unit "s" headline
        assert entry["step_ms"] == 100.0
        assert entry["tokens_per_sec"] == 1000
        assert entry["recorder_pct_of_step"] == 0.05
        assert entry["goodput_ledger"]["dominant"] == "compute"
        assert entry["drill_training_goodput"] == 0.95
        assert entry["preset"] == "default"
        assert entry["tpu_unavailable"] is False
        assert json.loads(json.dumps(entry)) == entry  # JSONL-safe

    def test_compile_observatory_columns(self, monkeypatch):
        """ISSUE 14 satellite: compile_s / cache_hit_ratio become flat
        gate-watched history columns when the observatory reported."""
        monkeypatch.setenv("DLROVER_TPU_BENCH_TIER1_DOTS", "902")
        result = _result()
        result["detail"]["compile_observatory"] = {
            "events": 3, "compile_s": 12.5, "cache_hits": 2,
            "cache_misses": 1, "cache_hit_ratio": 0.667,
            "stalls": 1, "by_trigger": {"first-trace": 3},
        }
        entry = bench._history_entry(result, preset="default")
        assert entry["compile_s"] == 12.5
        assert entry["cache_hit_ratio"] == 0.667
        assert entry["compile_observatory"]["by_trigger"] == {
            "first-trace": 3
        }
        # no lookups -> ratio None -> the column is simply absent
        result["detail"]["compile_observatory"]["cache_hit_ratio"] = None
        entry = bench._history_entry(result, preset="default")
        assert "cache_hit_ratio" not in entry
        from dlrover_tpu.observability.sentinel import BENCH_WATCH

        assert BENCH_WATCH["compile_s"] == "up"
        assert BENCH_WATCH["cache_hit_ratio"] == "down"

    def test_probe_outcome_recorded_on_degraded_round(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_BENCH_TIER1_DOTS", "0")
        entry = bench._history_entry(
            _result(tpu_down=True), preset="tiny"
        )
        assert entry["tpu_unavailable"] is True
        assert entry["tpu_probe"]["attempts"] == 4
        assert "wedged" in entry["tpu_probe"]["last_error"]

    def test_read_history_skips_corrupt_lines(self, tmp_path):
        path = tmp_path / "hist.jsonl"
        path.write_text(
            json.dumps({"a": 1}) + "\n"
            + "{torn line\n"
            + json.dumps({"b": 2}) + "\n"
        )
        assert bench._read_history(str(path)) == [{"a": 1}, {"b": 2}]

    def test_read_history_missing_file_is_empty(self, tmp_path):
        assert bench._read_history(str(tmp_path / "nope.jsonl")) == []


class TestHistoryAndGate:
    def _seed_history(self, path, rounds=10, step_ms=100.0):
        with open(path, "w") as f:
            for _ in range(rounds):
                entry = bench._history_entry(
                    _result(step_ms=step_ms), preset="default"
                )
                f.write(json.dumps(entry) + "\n")

    def test_appends_and_cold_gate_passes(self, tmp_path, monkeypatch):
        path = str(tmp_path / "hist.jsonl")
        monkeypatch.setenv("DLROVER_TPU_BENCH_HISTORY", path)
        monkeypatch.setenv("DLROVER_TPU_BENCH_TIER1_DOTS", "1")
        result = _result()
        assert bench._history_and_gate(result, "default") is False
        entries = bench._read_history(path)
        assert len(entries) == 1
        assert entries[0]["regression_gate"]["ok"] is True
        assert result["detail"]["regression_gate"]["ok"] is True

    def test_regression_flagged_but_soft_by_default(self, tmp_path,
                                                    monkeypatch):
        path = str(tmp_path / "hist.jsonl")
        monkeypatch.setenv("DLROVER_TPU_BENCH_HISTORY", path)
        monkeypatch.setenv("DLROVER_TPU_BENCH_TIER1_DOTS", "1")
        monkeypatch.setenv("DLROVER_TPU_SENTINEL_MIN_SAMPLES", "4")
        self._seed_history(path)
        result = _result(step_ms=300.0)  # 3x step time
        gate_failed = bench._history_and_gate(result, "default")
        verdict = result["detail"]["regression_gate"]
        assert "step_ms" in verdict["regressions"]
        assert gate_failed is False  # loud, not fatal, by default

    def test_hard_gate_flips_exit(self, tmp_path, monkeypatch):
        path = str(tmp_path / "hist.jsonl")
        monkeypatch.setenv("DLROVER_TPU_BENCH_HISTORY", path)
        monkeypatch.setenv("DLROVER_TPU_BENCH_TIER1_DOTS", "1")
        monkeypatch.setenv("DLROVER_TPU_SENTINEL_MIN_SAMPLES", "4")
        monkeypatch.setenv("DLROVER_TPU_BENCH_REGRESSION_GATE", "1")
        self._seed_history(path)
        assert bench._history_and_gate(
            _result(step_ms=300.0), "default"
        ) is True
        # the regression round is still appended (the trajectory must
        # record the bad round it failed on)
        assert len(bench._read_history(path)) == 11

    def test_stable_round_passes_hard_gate(self, tmp_path, monkeypatch):
        path = str(tmp_path / "hist.jsonl")
        monkeypatch.setenv("DLROVER_TPU_BENCH_HISTORY", path)
        monkeypatch.setenv("DLROVER_TPU_BENCH_TIER1_DOTS", "1")
        monkeypatch.setenv("DLROVER_TPU_SENTINEL_MIN_SAMPLES", "4")
        monkeypatch.setenv("DLROVER_TPU_BENCH_REGRESSION_GATE", "1")
        self._seed_history(path)
        assert bench._history_and_gate(
            _result(step_ms=101.0), "default"
        ) is False

    def test_degraded_round_not_judged_by_hw_history(self, tmp_path,
                                                     monkeypatch):
        path = str(tmp_path / "hist.jsonl")
        monkeypatch.setenv("DLROVER_TPU_BENCH_HISTORY", path)
        monkeypatch.setenv("DLROVER_TPU_BENCH_TIER1_DOTS", "1")
        monkeypatch.setenv("DLROVER_TPU_SENTINEL_MIN_SAMPLES", "4")
        monkeypatch.setenv("DLROVER_TPU_BENCH_REGRESSION_GATE", "1")
        self._seed_history(path)
        degraded = _result(step_ms=5000.0, tpu_down=True)
        assert bench._history_and_gate(degraded, "tiny") is False
        verdict = degraded["detail"]["regression_gate"]
        assert verdict["comparable_rounds"] == 0


class TestTier1Dots:
    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_BENCH_TIER1_DOTS", "123")
        assert bench._tier1_dots() == 123

    def test_malformed_env_never_kills_the_gate(self, tmp_path,
                                                monkeypatch):
        """The bench's one JSON line must print no matter what: a
        driver exporting DLROVER_TPU_BENCH_TIER1_DOTS='' (to 'unset'
        it) must not crash history construction."""
        monkeypatch.setenv("DLROVER_TPU_BENCH_TIER1_DOTS", "")
        monkeypatch.setenv(
            "DLROVER_TPU_BENCH_HISTORY", str(tmp_path / "h.jsonl")
        )
        result = _result()
        assert bench._history_and_gate(result, "default") is False
        entries = bench._read_history(str(tmp_path / "h.jsonl"))
        assert len(entries) == 1

    def test_unknown_without_log(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_BENCH_TIER1_DOTS", "-1")
        monkeypatch.setattr(
            "builtins.open",
            lambda *a, **k: (_ for _ in ()).throw(OSError()),
        )
        assert bench._tier1_dots() == -1


class TestPeakTable:
    def test_known_device(self):
        assert bench._peak_bf16_flops("TPU v5 lite") == 197e12

    def test_unknown_device_is_an_error_not_a_default(self):
        with pytest.raises(KeyError, match="no published bf16 peak"):
            bench._peak_bf16_flops("cpu")
