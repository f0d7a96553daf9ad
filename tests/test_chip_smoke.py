"""chip_smoke.py's contract, as far as a machine without a chip can hold
it to: the orchestrator and the launcher stay off JAX, a bare copy of the
script fails, and a rehearsal can never print the ``ok`` line."""

import argparse
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _ok_lines(stdout):
    found = []
    for line in stdout.splitlines():
        try:
            record = json.loads(line)
        except ValueError:
            continue
        if isinstance(record, dict) and record.get("ok") is True \
                and "device" in record:
            found.append(record)
    return found


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert not _ok_lines(proc.stdout)
    first = json.loads(proc.stdout.splitlines()[0])
    assert first["phase"] == "env" and first["ok"] is False
    assert "not importable" in first["error"]


def test_orchestrator_and_launcher_never_import_jax():
    """The chip belongs to one process at a time: whatever starts the
    workers must not hold it."""
    code = (
        "import sys, importlib.util\n"
        f"spec = importlib.util.spec_from_file_location('cs', {SCRIPT!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "import dlrover_tpu.trainer.elastic_run\n"
        "import dlrover_tpu.master.main\n"
        "import dlrover_tpu.agent.elastic_agent\n"
        "import dlrover_tpu.agent.ckpt_saver\n"
        "from dlrover_tpu.trainer.bootstrap import compile_cache_dir\n"
        "compile_cache_dir()\n"
        "print('jax' in sys.modules, 'jaxlib' in sys.modules)\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-1000:]
    assert proc.stdout.split() == ["False", "False"]


def test_rehearsal_lines_are_marked_and_carry_no_ok_line(smoke, capsys):
    smoke._emit({"phase": "final", "device": {"platform": "cpu"}}, True)
    smoke._emit({"phase": "env", "ok": True, "seconds": 1.0}, True)
    out = capsys.readouterr().out
    for line in out.splitlines():
        assert line.startswith("REHEARSAL ")
        assert json.loads(line[len("REHEARSAL "):])["rehearsal"] is True
    assert not _ok_lines(out)  # no line of it even parses as JSON


@pytest.mark.parametrize(
    "chips, rehearse, layers, attention",
    [(1, False, 8, "flash"), (4, False, 22, "flash"),
     (1, True, 2, "reference"), (4, True, 2, "reference")],
)
def test_sizes(smoke, chips, rehearse, layers, attention):
    """One chip trains the 1.24B widths cut to 8 layers (state + snapshot
    copy + step fit 16 GB), four chips the full 22; only a rehearsal
    names the reference attention."""
    sizes = smoke._sizes(argparse.Namespace(chips=chips, rehearse=rehearse))
    assert sizes["layers"] == layers
    assert sizes["attention"] == attention
    if not rehearse:
        assert (sizes["batch"], sizes["seq"]) == (4, 2048)
        assert sizes["model"] == "llama2_1b"


def test_a_worker_refuses_a_cpu_outside_a_rehearsal(smoke):
    args = argparse.Namespace(rehearse=False, chips=1)
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    with pytest.raises(RuntimeError, match="not tpu"):
        smoke._require_chip(args, cpu)
    smoke._require_chip(argparse.Namespace(rehearse=True, chips=1), cpu)
    one = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    smoke._require_chip(args, one)
    with pytest.raises(RuntimeError, match="wanted 4"):
        smoke._require_chip(argparse.Namespace(rehearse=False, chips=4), one)


def test_a_warning_far_above_the_tail_is_found(smoke, tmp_path):
    """The step-2 save logs long before the job ends: the scan for the
    engine's warning reads whole logs, the tails are for failure output."""
    early = tmp_path / "worker_0_0_r0.log"
    early.write_text(
        "on-device snapshot copy failed (boom); sync fallback\n"
        + "step line\n" * 2000
    )
    (tmp_path / "tpurun.log").write_text("nothing to see\n" * 10)
    assert "sync fallback" not in smoke._logs_tail(str(tmp_path))[early.name]
    assert smoke._logs_holding(str(tmp_path), "sync fallback") == [early.name]


def test_a_tmpdir_too_long_for_sockets_fails_env_by_name(smoke, tmp_path):
    """Runtime state stays under TMPDIR whatever its length: where that
    leaves a unix socket no room the env phase says so, and nothing
    falls back to /tmp."""
    args = argparse.Namespace(rehearse=True, chips=1)
    run = {"sockets": str(tmp_path / ("x" * smoke.UNIX_PATH_MAX))}
    with pytest.raises(smoke.PhaseFailed) as failed:
        smoke.phase_env(args, run)
    assert failed.value.error == "socket-dir-too-long"
    with open(SCRIPT) as f:
        assert '"/tmp' not in f.read()
