"""Persistent XLA compile-cache wiring in the worker bootstrap."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import os, json, jax
import dlrover_tpu.trainer as t
from dlrover_tpu.trainer import bootstrap
t.init(platform="cpu")
print("cache_dir=%r" % (jax.config.jax_compilation_cache_dir,))
print("cache_info=" + json.dumps(bootstrap.compile_cache_info()))
print("min_s=%r" % (
    jax.config.jax_persistent_cache_min_compile_time_secs,))
"""


def _run(env_extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("DLROVER_TPU_COMPILE_CACHE", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("DLROVER_TPU_MASTER_ADDR", None)
    env.update(env_extra)
    out = subprocess.run(
        [sys.executable, "-c", PROBE], env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-1000:]
    return out.stdout


class TestCompileCacheWiring:
    def test_cpu_default_off(self):
        """XLA:CPU AOT cache entries bake in host features (SIGILL risk
        across machines): CPU must not cache without explicit opt-in."""
        stdout = _run({})
        assert "cache_dir=None" in stdout or "cache_dir=''" in stdout

    def test_explicit_env_enables(self, tmp_path):
        cache = str(tmp_path / "xla_cache")
        stdout = _run({"DLROVER_TPU_COMPILE_CACHE": cache})
        assert f"cache_dir={cache!r}" in stdout
        assert os.path.isdir(cache)

    def test_off_sentinel_disables(self):
        stdout = _run({"DLROVER_TPU_COMPILE_CACHE": "off"})
        assert "cache_dir=None" in stdout or "cache_dir=''" in stdout

    def test_jax_env_dir_is_the_only_cache(self, tmp_path):
        """Where the machine sets JAX_COMPILATION_CACHE_DIR that
        directory is the cache: the code names no other, not even
        through its own knob."""
        theirs = str(tmp_path / "machine_cache")
        ours = str(tmp_path / "knob_cache")
        stdout = _run({
            "JAX_COMPILATION_CACHE_DIR": theirs,
            "DLROVER_TPU_COMPILE_CACHE": ours,
        })
        assert f"cache_dir={theirs!r}" in stdout
        info = _probe_info(stdout)
        assert info["enabled"] is True and info["dir"] == theirs
        assert not os.path.exists(ours)

    def test_off_wins_over_jax_env(self, tmp_path):
        stdout = _run({
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "machine_cache"),
            "DLROVER_TPU_COMPILE_CACHE": "off",
        })
        info = _probe_info(stdout)
        assert info["enabled"] is False and info["reason"] == "env-off"

    def test_default_dir_is_fixed_inside_the_checkout(self, monkeypatch):
        """Unset, the cache is .cache/xla beside pyproject.toml: never
        /tmp, never a name made from a pid, a time or a temp name (the
        path is part of the cache key)."""
        from dlrover_tpu.trainer import bootstrap

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.delenv("DLROVER_TPU_COMPILE_CACHE", raising=False)
        path = bootstrap.compile_cache_dir()
        assert path == os.path.join(REPO, ".cache", "xla")
        assert os.path.exists(os.path.join(REPO, "pyproject.toml"))
        assert path == bootstrap.compile_cache_dir()


def _probe_info(stdout):
    import json

    for line in stdout.splitlines():
        if line.startswith("cache_info="):
            return json.loads(line[len("cache_info="):])
    raise AssertionError(f"no cache_info line in {stdout!r}")


class TestCacheStatusRecorded:
    """ISSUE 14 satellite: the cache outcome must be VISIBLE — a
    status the compile observatory classifies against, a metric +
    flight-recorder event when the cache could not be enabled."""

    def test_enabled_status_and_min_compile_knob(self, tmp_path):
        cache = str(tmp_path / "xla_cache")
        stdout = _run({
            "DLROVER_TPU_COMPILE_CACHE": cache,
            "DLROVER_TPU_COMPILE_CACHE_MIN_S": "0.25",
        })
        info = _probe_info(stdout)
        assert info["enabled"] is True
        assert info["dir"] == cache
        assert info["entries_at_boot"] == 0
        assert "min_s=0.25" in stdout

    def test_entries_at_boot_counted(self, tmp_path):
        cache = tmp_path / "xla_cache"
        cache.mkdir()
        (cache / "jit_f-abc-cache").write_bytes(b"x")
        (cache / "jit_f-abc-atime").write_bytes(b"x")
        stdout = _run({"DLROVER_TPU_COMPILE_CACHE": str(cache)})
        info = _probe_info(stdout)
        assert info["entries_at_boot"] == 1  # -atime files excluded

    def test_cpu_default_off_reason(self):
        info = _probe_info(_run({}))
        assert info["enabled"] is False
        assert info["reason"] == "cpu-default-off"

    def test_disabled_emits_metric_and_flight_event(self):
        """In-process: a cache that cannot be configured counts a
        dlrover_tpu_compile_cache_disabled_total and drops a
        compile_cache.disabled event into the flight recorder."""
        from dlrover_tpu.observability import flight_recorder
        from dlrover_tpu.observability import metrics as obs_metrics
        from dlrover_tpu.trainer import bootstrap

        flight_recorder.recorder().reset()
        before = obs_metrics.registry().counter_total(
            "dlrover_tpu_compile_cache_disabled_total"
        )
        bootstrap._note_cache_disabled(  # noqa: SLF001 - the unit
            "config-error: boom", "/tmp/nope"
        )
        after = obs_metrics.registry().counter_total(
            "dlrover_tpu_compile_cache_disabled_total"
        )
        assert after == before + 1
        events = flight_recorder.recorder().snapshot(stacks=False)[
            "events"
        ]
        mine = [
            e for e in events
            if e.get("name") == "compile_cache.disabled"
        ]
        assert mine
        assert mine[-1]["content"]["reason"].startswith("config-error")
        assert bootstrap.compile_cache_info()["enabled"] is False

    def test_config_error_records_reason(self, tmp_path):
        """A file where the cache dir should be: makedirs fails, the
        warning keeps boot alive, and the status carries the reason."""
        blocker = tmp_path / "blocked"
        blocker.write_text("not a dir")
        stdout = _run({"DLROVER_TPU_COMPILE_CACHE": str(blocker)})
        info = _probe_info(stdout)
        assert info["enabled"] is False
        assert info["reason"].startswith("config-error")
