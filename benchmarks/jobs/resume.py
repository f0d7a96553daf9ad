"""The kill-and-resume job (cell ``*.kill_resume``): the orchestrator.

It never imports JAX.  ``tpurun --standalone --nproc_per_node=1`` (launcher,
agent and master of the program) starts ``resume_worker.py``, which holds
the chip.  Set-up is the first incarnation: steps, one MEMORY save that
lands, a few more steps whose losses are kept.  Then this process
``SIGKILL``s the worker, and the window opens at the kill.  The agent
notices, persists the snapshot, respawns the worker in place; the second
incarnation restores from shm, compiles through the persistent cache and
steps on.  ``resume_s`` is from the kill to the end of its first completed
step, on this host's one clock (``time.time()`` in both processes).
"""

import json
import os
import signal
import subprocess
import sys
import time

from benchmarks import common

WORKER = os.path.join(common.HERE, "jobs", "resume_worker.py")


def _read(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _logs(log_dir, tpurun_log):
    tails = {"tpurun.log": common.tail(tpurun_log, 2500)}
    try:
        for name in sorted(os.listdir(log_dir)):
            tails[name] = common.tail(os.path.join(log_dir, name), 2500)
    except OSError:
        pass
    return tails


def run(run):
    t_phase = run.begin("env")
    traffic = run.traffic
    how, libs = common.build_native()
    out = os.path.join(run.scratch, "out")
    log_dir = os.path.join(run.scratch, "logs")
    os.makedirs(out)
    os.makedirs(log_dir)
    spec_path = os.path.join(run.scratch, "spec.json")
    with open(spec_path, "w") as f:
        json.dump({
            "config": run.config, "traffic": traffic, "seed": run.seed,
            "trace": run.trace, "rehearse": run.rehearse, "chips": run.chips,
            "out": out, "ckpt": os.path.join(run.scratch, "ckpt"),
            "trace_dir": os.path.join(run.scratch, "trace"),
            "cell": run.cell["name"],
        }, f)
    env = run.program_env()
    env["DLROVER_TPU_JOB_NAME"] = f"bench{os.getpid()}"
    cmd = [
        sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
        "--standalone", "--nproc_per_node=1",
        f"--max-restarts={int(traffic['max_restarts'])}",
        f"--log-dir={log_dir}",
        *(["--platform=cpu"] if run.rehearse else []),
        WORKER, "--spec", spec_path,
    ]
    tpurun_log = os.path.join(log_dir, "tpurun.log")
    run.emit({"phase": "env", "ok": True, "native_build": how,
              "seconds": round(time.time() - t_phase, 2)})

    def fail(error, **detail):
        raise common.CheckFailed(error, logs=_logs(log_dir, tpurun_log),
                                 **detail)

    t_phase = run.begin("first_incarnation")
    job_deadline = time.time() + float(traffic["job_timeout_s"])
    r0_path = os.path.join(out, "r0.json")
    r1_path = os.path.join(out, "r1.json")
    with open(tpurun_log, "w") as log:
        proc = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                                env=env, cwd=common.ROOT,
                                start_new_session=True)
    try:
        first = None
        while first is None:
            if proc.poll() is not None:
                fail(f"tpurun exited {proc.returncode} before the save landed",
                     first=_read(r0_path))
            if time.time() > job_deadline:
                fail("the first incarnation did not get ready in time")
            time.sleep(0.05)
            first = _read(r0_path)
        if not first.get("ok"):
            fail(first.get("error", "the first incarnation failed"),
                 first=first)
        run.emit({"phase": "first_incarnation", "ok": True,
                  **{k: first.get(k) for k in (
                      "device", "params", "state_bytes", "losses",
                      "save_blocked_s", "save_landed_s", "step_compile_s",
                      "step_cache", "reference", "phases")},
                  "seconds": round(time.time() - t_phase, 2)})

        # -- the window opens at the kill ---------------------------------
        run.begin("window")
        kill_ts = time.time()
        os.kill(int(first["pid"]), signal.SIGKILL)
        setup_s = kill_ts - run.t_process_start
        second = None
        while second is None and time.time() < job_deadline:
            if proc.poll() is not None:
                second = _read(r1_path)
                break
            time.sleep(0.05)
            second = _read(r1_path)
        try:
            rc = proc.wait(timeout=max(1.0, job_deadline - time.time()))
        except subprocess.TimeoutExpired:
            rc = "timeout"
        second = second or _read(r1_path)
    finally:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.wait()
    if second is None:
        fail(f"the restarted worker left no record (tpurun exit {rc})")
    if not second.get("ok"):
        fail(second.get("error", "the restarted worker failed"),
             second=second)

    # -- the numbers ------------------------------------------------------
    resume_s = second["first_step_done_ts"] - kill_ts
    values = {
        "setup_s": setup_s,
        "resume_s": resume_s,
        "respawn_s": second["entry_ts"] - kill_ts,
        "boot_s": second["boot_done_ts"] - second["entry_ts"],
        "restore_s": second["restore_s"],
        "compile_s.resume": second["step_compile_s"],
        "step_ms.resume": 1e3 * second["step_s_after_first"],
    }
    peaks = [r.get("memory_peak_bytes") for r in (first, second)]
    peaks = [p for p in peaks if p]
    peak = max(peaks) if peaks else None
    if peak:
        values["hbm_peak_gib.resume"] = peak / 2 ** 30
    events = common.read_events(run.events_file)
    fallbacks = [e for e in events
                 if e.get("name") == "trainer.ckpt.sync_fallback"]
    after = [str(s) for s in second["steps_after_restore"]]
    same_losses = all(
        first["losses"].get(s) == second["losses"].get(s) for s in after
    ) and bool(after)
    in_time = resume_s <= run.seconds
    device = second["device"]
    chip_ok = run.rehearse or (
        device["platform"] == "tpu" and device["count"] == run.chips)
    correct = bool(
        same_losses and second["restore_source"] == "memory"
        and second["start_step"] == first["saved_step"]
        and not fallbacks and chip_ok and rc == 0
        and first.get("reference", {}).get("ok", False))
    run.emit({
        "phase": "window", "ok": correct, "tpurun_exit": rc,
        "kill_ts": kill_ts, "resume_s": resume_s, "in_time": in_time,
        "losses_first": {s: first["losses"].get(s) for s in after},
        "losses_resumed": {s: second["losses"].get(s) for s in after},
        "losses_bit_equal": same_losses,
        "restore_source": second["restore_source"],
        "restored_step": second["start_step"],
        "step_cache": second["step_cache"],
        "sync_fallbacks": len(fallbacks),
        "parts_s": {k: values[k] for k in (
            "respawn_s", "boot_s", "restore_s", "compile_s.resume")},
        "unattributed_s": resume_s - sum(values[k] for k in (
            "respawn_s", "boot_s", "restore_s", "compile_s.resume")),
        "phases": second.get("phases"),
        "values": values,
    })
    observed = {
        "values": values, "correct": correct, "attempted": 1,
        "failed": 0 if in_time else 1,
        "device": {**device, "memory_peak_bytes": peak},
        "trace": second.get("trace") or {},
    }
    reduced = observed["trace"]
    if run.trace and "busy_s" in reduced:
        observed["device"]["busy_s"] = reduced["busy_s"]
        observed["device"]["window_s"] = reduced["window_s"]
        observed["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
    if run.trace:
        run.emit({"phase": "trace", "ok": "busy_s" in reduced, **reduced})
    return observed
