#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the elastic training path still
starts on the chip.  A smoke, not a benchmark: it claims no rate.

Two roles in one file.

Run bare it is an ORCHESTRATOR that never imports JAX (a chip belongs to
one process at a time; the processes that need it are the workers it and
``tpurun`` start, one after another).  Phases on one chip::

    env     versions, /dev/shm room, compile-cache dir, native libs built
            from native/ and loaded
    kernel  FA2 forward+backward at (1,2048,16,128) bf16 vs the fp32
            reference
    train   tpurun --standalone: 8-layer Llama at the 1.24B widths, FA2 in
            the step, MEMORY save at step 2, DISK save at 4, hard exit at
            5, in-place restart, restore of step 4 from shm (and the
            DISK save read back and held to it, bit for bit), persistent-
            cache hit, on to step 8; then the same seed uninterrupted,
            and the losses of steps 5-8 compared
    sync    block_until_ready vs hard_block on one large matmul (a ratio)

``--chips 4`` runs the sharded path and what it is compared with, and no
other phase: the full 22-layer model on ``fsdp=4``, the same saves and
crash, the resume under ``dp=2, fsdp=2`` from the DISK save (the shm
segment is dropped before the exit), and placement asserted.

Run with ``--worker ROLE`` (the orchestrator and ``tpurun`` do that) it is
the training script.

``--rehearse`` walks the same control flow on the CPU with
``LlamaConfig.tiny()`` and the reference attention, named so here and
nowhere in the program.  Every line it prints says so, and it cannot
print the ``ok`` line.

The last line of a real run is ``{"ok": true, "device": {...}}`` with the
device as the worker that held the chip recorded it.  Every earlier line
is one JSON object per phase.
"""

import argparse
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
EXIT_CODES = {"env": 11, "kernel": 12, "train": 13, "sync": 14, "shard": 15}

#: stated tolerances
FWD_ATOL = 3e-2          # FA2 forward vs fp32 reference, bf16 inputs
BWD_REL = 5e-2           # FA2 grads: max abs err / max(1, max|ref grad|)
RESUME_RTOL = 1e-4       # resumed vs uninterrupted loss, same layout
RESHARD_RTOL = 2e-3      # resumed under another layout (other sum order)

TOTAL_STEPS = 8
MEMORY_SAVE_STEP = 2
DISK_SAVE_STEP = 4
CRASH_STEP = 5
CRASH_EXIT = 17
GIB = 1 << 30
#: seconds one tpurun job may take, by chips: on one chip both jobs stay
#: inside the smoke's 1200
JOB_TIMEOUT_S = {1: 500, 4: 1800}
UNIX_PATH_MAX = 107      # sun_path holds 108 bytes, one of them the NUL
SOCKET_NAME_ROOM = 48    # the longest socket name of a job here has 36


class PhaseFailed(Exception):
    def __init__(self, phase, error, detail=None):
        super().__init__(error)
        self.phase = phase
        self.error = error
        self.detail = detail or {}


# ---------------------------------------------------------------------------
# orchestrator (never imports jax)
# ---------------------------------------------------------------------------


def _emit(record, rehearse):
    if rehearse:
        record = {"rehearsal": True, **record}
        print("REHEARSAL " + json.dumps(record), flush=True)
    else:
        print(json.dumps(record), flush=True)


def _tail(path, limit=3000):
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - limit))
            return f.read().decode("utf-8", errors="replace")
    except OSError as e:
        return f"<no log: {e}>"


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def _sizes(args):
    """What each mode trains, in one place."""
    if args.rehearse:
        return {"model": "tiny", "layers": 2, "batch": 8, "seq": 64,
                "attention": "reference", "shm_need": 64 << 20}
    if args.chips == 4:
        return {"model": "llama2_1b", "layers": 22, "batch": 4, "seq": 2048,
                "attention": "flash", "shm_need": 11 * GIB}
    return {"model": "llama2_1b", "layers": 8, "batch": 4, "seq": 2048,
            "attention": "flash", "shm_need": 5 * GIB}


def _build_native():
    """Both libraries rebuilt from native/ into native/build/: cmake,
    else the one-translation-unit direct compile timer/core.py uses."""
    src = os.path.join(HERE, "native")
    build = os.path.join(src, "build")
    shutil.rmtree(build, ignore_errors=True)
    how = "cmake"
    try:
        subprocess.run(["cmake", "-S", src, "-B", build], check=True,
                       capture_output=True, timeout=300)
        subprocess.run(["cmake", "--build", build], check=True,
                       capture_output=True, timeout=600)
    except (OSError, subprocess.SubprocessError) as e:
        how = f"direct (cmake: {type(e).__name__})"
        shutil.rmtree(build, ignore_errors=True)
        os.makedirs(build)
        cxx = next((c for c in ("c++", "g++", "clang++")
                    if shutil.which(c)), None)
        if cxx is None:
            raise PhaseFailed("env", "no cmake and no C++ compiler")
        for lib, unit in (("tpu_timer", "tpu_timer/tpu_timer.cc"),
                          ("fastcopy", "fastcopy/fastcopy.cc")):
            proc = subprocess.run(
                [cxx, "-std=c++17", "-O2", "-shared", "-fPIC",
                 os.path.join(src, unit), "-o",
                 os.path.join(build, f"lib{lib}.so"), "-lpthread"],
                capture_output=True, text=True, timeout=600,
            )
            if proc.returncode != 0:
                raise PhaseFailed(
                    "env", f"native build of {lib} failed",
                    {"stderr": proc.stderr[-1500:]},
                )
    import ctypes

    libs = {}
    for lib in ("tpu_timer", "fastcopy"):
        path = os.path.join(build, f"lib{lib}.so")
        try:
            ctypes.CDLL(path)
        except OSError as e:
            raise PhaseFailed("env", f"built {path} does not load: {e}")
        libs[lib] = path
    return how, libs


def phase_env(args, run):
    from importlib import metadata

    versions = {"python": sys.version.split()[0]}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            if not args.rehearse:
                raise PhaseFailed("env", f"package {pkg} is not installed")
            versions[pkg] = "absent"
    try:
        from dlrover_tpu.trainer.bootstrap import compile_cache_dir
    except ImportError as e:
        raise PhaseFailed("env", f"the program is not importable: {e}")
    shm_free = shutil.disk_usage("/dev/shm").free
    need = _sizes(args)["shm_need"]
    if shm_free < need:
        raise PhaseFailed(
            "env", "shm-too-small",
            {"shm_free_bytes": shm_free, "shm_need_bytes": need},
        )
    if len(run["sockets"]) + 1 + SOCKET_NAME_ROOM > UNIX_PATH_MAX:
        raise PhaseFailed(
            "env", "socket-dir-too-long",
            {"socket_dir": run["sockets"],
             "longest_allowed": UNIX_PATH_MAX - 1 - SOCKET_NAME_ROOM,
             "hint": "unix socket paths are short: point TMPDIR at a "
                     "shorter directory"},
        )
    cache_dir = compile_cache_dir()
    try:
        entries = sum(1 for n in os.listdir(cache_dir)
                      if n.endswith("-cache"))
    except OSError:
        entries = 0
    how, libs = _build_native()
    run["native_libs"] = libs
    return {
        "versions": versions, "shm_free_bytes": shm_free,
        "shm_need_bytes": need, "compile_cache_dir": cache_dir,
        "compile_cache_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
        ),
        "compile_cache_entries_at_start": entries,
        "native_build": how, "native_libs": libs,
    }


def _child_env(args, run, extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("DLROVER_TPU_MASTER_ADDR", None)
    scratch = run["scratch"]
    env.update({
        # runtime IPC state lives in directories this script removes
        "DLROVER_TPU_SOCKET_DIR": run["sockets"],
        "DLROVER_TPU_EVENT_FILE": run["events"],
        "DLROVER_TPU_INCIDENT_DIR": os.path.join(scratch, "incidents"),
        "DLROVER_TPU_LOG_DIR": os.path.join(scratch, "hang"),
        "DLROVER_TPU_JOB_STATE_DIR": os.path.join(scratch, "jobs"),
        "DLROVER_TPU_PARAL_CONFIG_PATH": os.path.join(scratch, "paral.json"),
        "DLROVER_TPU_RUNTIME_METRICS_PATH": os.path.join(
            scratch, "runtime_metrics.json"
        ),
        "TPU_LOG_DIR": "disabled",
    })
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            ).strip()
        # the tiny state is below the engine's async floor; the rehearsal
        # is of the asynchronous path, and of the persistent cache (a CPU
        # backend caches only where a directory is named: a throw-away
        # one, CPU entries have no place in the real cache)
        env["DLROVER_TPU_ASYNC_MIN_BYTES"] = "0"
        env["DLROVER_TPU_COMPILE_CACHE_MIN_S"] = "0"
    env.update(extra or {})
    return env


def _worker_cmd(args, run, role, *more):
    cmd = [os.path.abspath(__file__), "--worker", role,
           "--out", run["out"], "--chips", str(args.chips)]
    if args.rehearse:
        cmd.append("--rehearse")
    return cmd + list(more)


def _run_plain_worker(args, run, phase, timeout):
    """One worker process, started directly (kernel, sync)."""
    log = os.path.join(run["out"], f"{phase}.log")
    result = os.path.join(run["out"], f"{phase}.json")
    with open(log, "w") as f:
        try:
            proc = subprocess.run(
                [sys.executable] + _worker_cmd(args, run, phase),
                stdout=f, stderr=subprocess.STDOUT, timeout=timeout,
                env=_child_env(args, run), cwd=HERE,
            )
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            rc = "timeout"
    if not os.path.exists(result):
        raise PhaseFailed(
            phase, f"worker exited {rc} and left no record",
            {"log_tail": _tail(log)},
        )
    rec = _read_json(result)
    if rc != 0 or not rec.get("ok"):
        raise PhaseFailed(
            phase, rec.get("error", f"worker exited {rc}"),
            {"worker_exit": rc, "worker": rec, "log_tail": _tail(log)},
        )
    run["device"] = rec["device"]
    rec.pop("ok")
    return rec


def _tpurun(args, run, tag, crash_at, timeout):
    """One ``tpurun --standalone --nproc_per_node=1`` job on the worker
    role; returns (rc, seconds, log_dir)."""
    log_dir = os.path.join(run["out"], f"logs_{tag}")
    os.makedirs(log_dir, exist_ok=True)
    ckpt_dir = os.path.join(run["scratch"], f"ckpt_{tag}")
    cmd = [
        sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
        "--standalone", "--nproc_per_node=1", "--max-restarts=2",
        f"--log-dir={log_dir}",
        *(["--platform=cpu"] if args.rehearse else []),
        *_worker_cmd(args, run, "train", "--tag", tag, "--ckpt", ckpt_dir,
                     "--crash-at", str(crash_at)),
    ]
    env = _child_env(args, run, {
        "DLROVER_TPU_JOB_NAME": f"smoke{os.getpid()}{tag}",
    })
    t0 = time.time()
    with open(os.path.join(log_dir, "tpurun.log"), "w") as f:
        try:
            rc = subprocess.run(
                cmd, stdout=f, stderr=subprocess.STDOUT, timeout=timeout,
                env=env, cwd=HERE,
            ).returncode
        except subprocess.TimeoutExpired:
            rc = "timeout"
    return rc, round(time.time() - t0, 1), log_dir


def _logs_tail(log_dir):
    tails = {}
    for name in sorted(os.listdir(log_dir)):
        tails[name] = _tail(os.path.join(log_dir, name), 2000)
    return tails


def _logs_holding(log_dir, needle):
    """Names of the logs that hold ``needle`` on any line: read whole,
    since what a save at step 2 logged is far above any tail."""
    needle = needle.encode()
    found = []
    for name in sorted(os.listdir(log_dir)):
        with open(os.path.join(log_dir, name), "rb") as f:
            if any(needle in line for line in f):
                found.append(name)
    return found


def _events(run):
    out = []
    try:
        with open(run["events"]) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return out


def phase_train(args, run, phase="train"):
    """The crash run, then the uninterrupted run of the same seed."""
    sizes = _sizes(args)
    rtol = RESHARD_RTOL if args.chips == 4 else RESUME_RTOL

    def fail(error, **detail):
        raise PhaseFailed(phase, error, detail)

    rc, crash_s, crash_logs = _tpurun(
        args, run, "crash", CRASH_STEP, timeout=JOB_TIMEOUT_S[args.chips]
    )
    if rc != 0:
        fail(f"tpurun (crash run) exited {rc}", logs=_logs_tail(crash_logs))
    try:
        first = _read_json(os.path.join(run["out"], "train_crash_r0.json"))
        second = _read_json(os.path.join(run["out"], "train_crash_r1.json"))
    except (OSError, ValueError) as e:
        fail(f"an incarnation left no record: {e}",
             logs=_logs_tail(crash_logs))
    run["device"] = second["device"]
    for rec in (first, second):
        if not rec.get("ok"):
            fail(rec.get("error", "worker reported failure"), worker=rec,
                 logs=_logs_tail(crash_logs))
    # the first incarnation: fresh, steps 1..5, then the hard exit
    if first["start_step"] != 0 or first["restore_source"] != "fresh":
        fail("first incarnation did not start fresh", worker=first)
    if sorted(map(int, first["losses"])) != list(range(1, CRASH_STEP + 1)):
        fail("first incarnation did not run steps 1-5", worker=first)
    if not first["crashed"]:
        fail("first incarnation did not hard-exit", worker=first)
    # the second: step 4 again, a persistent-cache hit, on to 8.  One
    # chip restores from shm and holds the DISK save to it, bit for bit;
    # four resume under the other layout from the DISK save itself (the
    # first incarnation dropped its segment, as a replaced host has none)
    if second["start_step"] != DISK_SAVE_STEP:
        fail(f"restart restored step {second['start_step']}, not "
             f"{DISK_SAVE_STEP}", worker=second)
    source = "memory" if args.chips == 1 else "storage"
    if second["restore_source"] != source:
        fail(f"restart restored from {second['restore_source']}, not "
             f"from {source}", worker=second)
    readback = second.get("storage_readback")
    if args.chips == 1 and not (
            readback and readback["step"] == DISK_SAVE_STEP
            and readback["bit_equal"]):
        fail("the DISK save read back is not the state restored from shm",
             worker=second)
    want = list(range(DISK_SAVE_STEP + 1, TOTAL_STEPS + 1))
    if sorted(map(int, second["losses"])) != want:
        fail("second incarnation did not run steps 5-8", worker=second)
    if args.chips == 1 and second["step_cache_hits"] < 1:
        # four chips: the restart compiles ANOTHER program (new layout);
        # its hit is the uninterrupted run's, checked below
        fail("no persistent-cache hit for trainer.train_step after the "
             "restart", worker=second)
    # saves: both asynchronous, no synchronous fallback
    events = _events(run)
    saves = {
        int(e["content"]["step"]): e["content"] for e in events
        if e.get("name") == "trainer.ckpt.save"
        and e.get("pid") == first["pid"]
    }
    fallbacks = [e for e in events
                 if e.get("name") == "trainer.ckpt.sync_fallback"]
    warned = _logs_holding(crash_logs, "sync fallback")
    if fallbacks or warned:
        fail("a save fell back to the synchronous path",
             events=fallbacks, logs_with_warning=warned)
    for step, storage in ((MEMORY_SAVE_STEP, False), (DISK_SAVE_STEP, True)):
        got = saves.get(step)
        if not got or not got.get("async") or got.get("storage") != storage:
            fail(f"save at step {step} was not the asynchronous "
                 f"{'DISK' if storage else 'MEMORY'} save", saves=saves)
    for rec in (first, second):
        if not args.rehearse and not rec["tpu_custom_call"]:
            fail("the lowered step holds no tpu_custom_call", worker=rec)
        for lib, path in run["native_libs"].items():
            if rec["native"].get(lib) != path:
                fail(f"worker did not use the built {lib}",
                     worker_native=rec["native"], built=path)
    if args.chips == 4:
        for rec in (first, second):
            place = rec["placement"]
            if place["min_param_devices"] != 4:
                fail("a parameter does not cover four devices",
                     placement=place)
            if (not args.rehearse
                    and place["bytes_in_use_max_over_min"] > 2.0):
                fail("bytes_in_use differs by more than 2x across chips",
                     placement=place)
    # the uninterrupted run of the same seed
    rc, ref_s, ref_logs = _tpurun(
        args, run, "ref", -1, timeout=JOB_TIMEOUT_S[args.chips]
    )
    if rc != 0:
        fail(f"tpurun (uninterrupted run) exited {rc}",
             logs=_logs_tail(ref_logs))
    try:
        ref = _read_json(os.path.join(run["out"], "train_ref_r0.json"))
    except (OSError, ValueError) as e:
        fail(f"the uninterrupted run left no record: {e}",
             logs=_logs_tail(ref_logs))
    if not ref.get("ok"):
        fail(ref.get("error", "worker reported failure"), worker=ref)
    if ref["step_cache_hits"] < 1:
        fail("the uninterrupted run (same program, warm cache) reported "
             "no persistent-cache hit", worker=ref)
    compared = {}
    for step in map(str, want):
        a, b = second["losses"][step], ref["losses"][step]
        compared[step] = {"resumed": a, "uninterrupted": b}
        if abs(a - b) > rtol * abs(b):
            fail(f"loss at step {step} differs beyond rtol={rtol}",
                 losses=compared)
    # the comparison must be able to tell a restore from a fresh start,
    # whose "step 5" would show the loss of step 1
    moved = abs(ref["losses"][str(CRASH_STEP)] - ref["losses"]["1"])
    if moved <= 2 * rtol * abs(ref["losses"]["1"]):
        fail("the loss moved too little for the comparison to mean "
             "anything", losses=ref["losses"])
    out = {
        "model": sizes, "params": first["params"],
        "crash_run_seconds": crash_s, "uninterrupted_run_seconds": ref_s,
        "compile_seconds_cold": first["step_compile_s"],
        "compile_seconds_restart": second["step_compile_s"],
        "compile_seconds_warm_run": ref["step_compile_s"],
        "step_cache": {
            "first": first["step_cache"], "restart": second["step_cache"],
            "uninterrupted": ref["step_cache"],
        },
        "cache_hits_first_incarnation": first["step_cache_hits"],
        "saves": {str(k): v for k, v in sorted(saves.items())},
        "save_landed_seconds": first.get("save_landed_s"),
        "state_bytes": first["params"] * 8,  # fp32 masters + 2 bf16 moments
        "restore": {"step": second["start_step"],
                    "source": second["restore_source"],
                    "seconds": second["restore_s"],
                    "layout": second["layout"]},
        "storage_readback": readback,
        "layout_first": first["layout"],
        "losses_first": first["losses"], "losses_resumed": second["losses"],
        "losses_uninterrupted": ref["losses"], "rtol": rtol,
        "step_seconds_resumed": second["step_seconds"],
        "tpu_custom_call": first["tpu_custom_call"],
        "native": second["native"],
    }
    if args.chips == 4:
        out["placement_first"] = first["placement"]
        out["placement_resumed"] = second["placement"]
    return out


def orchestrate(args):
    out = os.path.abspath(
        args.out or os.path.join(
            HERE, "chiprun_out",
            "chip_smoke" + ("_rehearsal" if args.rehearse else "")
            + (f"_{args.chips}chips" if args.chips != 1 else ""),
        )
    )
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    # both under TMPDIR and nowhere else; the sockets beside the scratch
    # directory and not inside it, because unix socket paths are short
    # (the env phase fails by name if TMPDIR leaves them no room)
    scratch = tempfile.mkdtemp(prefix="dlrsmoke_")
    sockets = tempfile.mkdtemp(prefix="s")
    run = {"out": out, "scratch": scratch, "sockets": sockets,
           "events": os.path.join(scratch, "events.jsonl")}
    if args.rehearse:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        os.environ["DLROVER_TPU_COMPILE_CACHE"] = os.path.join(scratch, "xla")
    if args.chips == 4:
        phases = [("env", phase_env),
                  ("shard", lambda a, r: phase_train(a, r, "shard"))]
    else:
        phases = [
            ("env", phase_env),
            ("kernel", lambda a, r: _run_plain_worker(a, r, "kernel", 300)),
            ("train", phase_train),
            ("sync", lambda a, r: _run_plain_worker(a, r, "sync", 200)),
        ]
    t_all = time.time()
    try:
        for name, fn in phases:
            t0 = time.time()
            try:
                detail = fn(args, run)
            except PhaseFailed as e:
                _emit({"phase": e.phase, "ok": False, "error": e.error,
                       "seconds": round(time.time() - t0, 1), **e.detail},
                      args.rehearse)
                return EXIT_CODES[name]
            _emit({"phase": name, "ok": True,
                   "seconds": round(time.time() - t0, 1), **detail},
                  args.rehearse)
        device = run.get("device")
        if not device:
            _emit({"phase": "final", "ok": False,
                   "error": "no worker recorded a device"}, args.rehearse)
            return 16
        if args.rehearse:
            _emit({"phase": "final", "phases_passed": [n for n, _ in phases],
                   "seconds": round(time.time() - t_all, 1),
                   "device": device}, True)
            return 0
        if device["platform"] != "tpu" or device["count"] != args.chips:
            _emit({"phase": "final", "ok": False,
                   "error": f"device record is {device}"}, False)
            return 16
        _emit({"phase": "total", "ok": True,
               "seconds": round(time.time() - t_all, 1)}, False)
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
        shutil.rmtree(sockets, ignore_errors=True)


# ---------------------------------------------------------------------------
# worker roles (these hold the chip)
# ---------------------------------------------------------------------------


def _device_record(jax):
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _write_result(args, name, record):
    path = os.path.join(args.out, name)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=1, default=str)
    os.replace(tmp, path)


def _require_chip(args, device):
    if args.rehearse:
        return
    if device["platform"] != "tpu":
        raise RuntimeError(
            f"platform is {device['platform']!r}, not tpu: nothing here "
            "runs on a CPU outside --rehearse"
        )
    if device["count"] != args.chips:
        raise RuntimeError(
            f"{device['count']} device(s), wanted {args.chips}"
        )


def worker_kernel(args):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.attention import flash_attention, reference_attention

    device = _device_record(jax)
    _require_chip(args, device)
    shape = (1, 256, 4, 64) if args.rehearse else (1, 2048, 16, 128)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys[:3])
    w = jax.random.normal(keys[3], shape, jnp.float32)
    mask = jnp.tril(jnp.ones((shape[1], shape[1]), bool))[None, None]

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=args.rehearse)
        return (out.astype(jnp.float32) * w).sum(), out

    def ref_loss(q, k, v):
        out = reference_attention(q, k, v, mask)
        return (out * w).sum(), out

    t0 = time.time()
    (_, out), grads = jax.jit(
        jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True)
    )(q, k, v)
    jax.block_until_ready(grads)
    kernel_s = time.time() - t0
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    (_, ref_out), ref_grads = jax.jit(
        jax.value_and_grad(ref_loss, argnums=(0, 1, 2), has_aux=True)
    )(*f32)
    fwd_err = float(jnp.abs(out.astype(jnp.float32) - ref_out).max())
    bwd = {}
    for name, g, r in zip("qkv", grads, ref_grads):
        scale = max(1.0, float(jnp.abs(r).max()))
        bwd[f"d{name}"] = float(
            jnp.abs(g.astype(jnp.float32) - r).max()
        ) / scale
    finite = bool(jnp.isfinite(out.astype(jnp.float32)).all()) and all(
        bool(jnp.isfinite(g.astype(jnp.float32)).all()) for g in grads
    )
    ok = finite and fwd_err <= FWD_ATOL and max(bwd.values()) <= BWD_REL
    return {
        "ok": ok, "device": device, "shape": list(shape), "dtype": "bfloat16",
        "interpret": bool(args.rehearse), "finite": finite,
        "fwd_max_abs_err": fwd_err, "fwd_atol": FWD_ATOL,
        "bwd_rel_err": bwd, "bwd_rel_tol": BWD_REL,
        "compile_and_run_seconds": round(kernel_s, 2),
        **({} if ok else {"error": "FA2 disagrees with the reference"}),
    }


def worker_sync(args):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.utils.timing import hard_block

    device = _device_record(jax)
    _require_chip(args, device)
    n, reps = (256, 4) if args.rehearse else (8192, 16)

    @jax.jit
    def chain(a):
        def body(_, acc):
            return (acc @ a).astype(a.dtype) * 0.5

        return jax.lax.fori_loop(0, reps, body, a)

    a = jax.random.normal(jax.random.PRNGKey(args.seed), (n, n),
                          jnp.bfloat16) * 0.01
    hard_block(chain(a))  # compile
    t0 = time.perf_counter()
    jax.block_until_ready(chain(a))
    bur_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    hard_block(chain(a))
    hard_s = time.perf_counter() - t0
    return {
        "ok": True, "device": device, "matmul": [n, n, n], "chained": reps,
        "block_until_ready_seconds": bur_s, "hard_block_seconds": hard_s,
        "hard_over_block_until_ready": hard_s / bur_s,
        "note": "decides nothing here; ~1 means block_until_ready waits "
                "for the device",
    }


def _placement(jax, state):
    leaves = jax.tree.leaves(state.params)
    per_leaf = [len({s.device for s in x.addressable_shards}) for x in leaves]
    in_use = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats and "bytes_in_use" in stats:
            in_use.append(int(stats["bytes_in_use"]))
    return {
        "param_leaves": len(leaves),
        "min_param_devices": min(per_leaf),
        "bytes_in_use": in_use or "not reported by this backend",
        "bytes_in_use_max_over_min": (
            max(in_use) / max(1, min(in_use)) if in_use else None
        ),
    }


def worker_train(args):
    """The training script ``tpurun`` launches (both incarnations, and
    the uninterrupted run)."""
    import dlrover_tpu.trainer as trainer_pkg

    ctx = trainer_pkg.init()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.common import fastcopy
    from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from dlrover_tpu.observability import jitscope
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.timer import get_timer
    from dlrover_tpu.trainer.bootstrap import compile_cache_info
    from dlrover_tpu.trainer.flash_checkpoint import Checkpointer, StorageType
    from dlrover_tpu.trainer.optim import create_optimizer
    from dlrover_tpu.trainer.train import Trainer

    restart = ctx.restart_count
    name = f"train_{args.tag}_r{restart}.json"
    rec = {"ok": False, "pid": os.getpid(), "restart": restart,
           "tag": args.tag, "crashed": False}
    try:
        device = _device_record(jax)
        rec["device"] = device
        _require_chip(args, device)
        sizes = _sizes(args)
        if sizes["model"] == "tiny":
            cfg = LlamaConfig.tiny(attention_impl=sizes["attention"])
        else:
            # the published widths; depth is what gets cut to fit
            cfg = dataclasses.replace(
                LlamaConfig.llama2_1b(
                    max_seq_len=sizes["seq"],
                    attention_impl=sizes["attention"],
                ),
                num_layers=sizes["layers"],
            )
        if args.chips == 4:
            resumed = args.tag == "crash" and restart > 0
            layout = ({"dp": 2, "fsdp": 2} if resumed else {"fsdp": 4})
        else:
            layout = {"dp": jax.device_count()}
        rec["layout"] = layout
        mesh = build_mesh(MeshConfig(**{"dp": 1, **layout}))
        model = LlamaForCausalLM(cfg)
        # the optimizer and dtypes the benchmark's cells train with
        opt = create_optimizer(
            peak_lr=3e-4, warmup_steps=10, total_steps=10_000,
            moment_dtype=jnp.bfloat16,
        )
        trainer = Trainer(model, opt, mesh, grads_dtype=jnp.bfloat16)
        rng = np.random.default_rng(args.seed)
        ids = rng.integers(
            0, cfg.vocab_size, size=(sizes["batch"], sizes["seq"] + 1)
        )
        host_batch = {"input_ids": np.asarray(ids[:, :-1], np.int32),
                      "labels": np.asarray(ids[:, 1:], np.int32)}
        init_rng = jax.random.PRNGKey(args.seed)
        sample = host_batch["input_ids"]
        ckpt = Checkpointer(args.ckpt)
        t0 = time.time()
        shardings = trainer.state_sharding_for(init_rng, sample)
        abstract = trainer.abstract_state(init_rng, sample)
        state, start_step = ckpt.load_checkpoint(abstract, shardings)
        rec["restore_s"] = round(time.time() - t0, 2)
        if state is None:
            state = trainer.create_state(init_rng, sample)
            start_step = 0
            rec["restore_source"] = "fresh"
        else:
            trainer.state_shardings = shardings
            rec["restore_source"] = _last_load_source()
            if rec["restore_source"] == "memory":
                # the DISK save is part of what passes: read it back
                # and hold it to the state that came from shm
                t0 = time.time()
                disk, disk_step = ckpt.engine.load_from_storage(
                    abstract, shardings
                )
                rec["storage_readback"] = {
                    "step": int(disk_step),
                    "bit_equal": disk is not None and all(
                        bool(jnp.array_equal(a, b)) for a, b in zip(
                            jax.tree.leaves(state), jax.tree.leaves(disk)
                        )
                    ),
                    "leaves": len(jax.tree.leaves(state)),
                    "seconds": round(time.time() - t0, 2),
                }
                del disk
        rec["start_step"] = int(start_step)
        rec["params"] = int(model.num_params())
        batch = trainer.shard_batch(host_batch)
        losses, step_seconds = {}, {}
        rec.update(losses=losses, step_seconds=step_seconds)
        saving = args.crash_at > 0
        for step in range(start_step + 1, TOTAL_STEPS + 1):
            t0 = time.time()
            state, metrics = trainer.train_step(state, batch)
            loss = float(jax.device_get(metrics["loss"]))
            step_seconds[str(step)] = round(time.time() - t0, 3)
            losses[str(step)] = loss
            print(f"step={step} loss={loss:.6f} "
                  f"s={step_seconds[str(step)]}", flush=True)
            if not np.isfinite(loss):
                raise RuntimeError(f"loss at step {step} is {loss}")
            if step == start_step + 1:
                _record_compile(rec, jitscope)
                text = trainer.lower_train_step(state, batch).as_text()
                rec["tpu_custom_call"] = "tpu_custom_call" in text
            if step == args.crash_at and restart == 0:
                rec.update(
                    ok=True, crashed=True,
                    native=_native_in_use(get_timer, fastcopy),
                    cache=compile_cache_info(),
                )
                if args.chips == 4:
                    rec["placement"] = _placement(jax, state)
                    # a layout changes when the hosts do, and a new host
                    # has no segment: drop it, so that the resume under
                    # the other layout reads the DISK save
                    ckpt.engine.unlink_memory()
                _write_result(args, name, rec)
                print(f"hard exit at step {step}", flush=True)
                os._exit(CRASH_EXIT)
            if saving and step in (MEMORY_SAVE_STEP, DISK_SAVE_STEP):
                kind = (StorageType.DISK if step == DISK_SAVE_STEP
                        else StorageType.MEMORY)
                t0 = time.time()
                blocked = ckpt.save_checkpoint(step, state, kind)
                # let the snapshot land before the next is asked for: the
                # busy-slot fallback then cannot fire, and a fallback
                # event can only mean a failed device copy
                if not ckpt.wait_latest_checkpoint(timeout=900):
                    raise RuntimeError(f"save at step {step} did not land")
                rec.setdefault("save_blocked_s", {})[str(step)] = round(
                    blocked, 4
                )
                rec.setdefault("save_landed_s", {})[str(step)] = round(
                    time.time() - t0, 2
                )
        if args.chips == 4:
            rec["placement"] = _placement(jax, state)
        rec["native"] = _native_in_use(get_timer, fastcopy)
        rec["cache"] = compile_cache_info()
        ckpt.engine.unlink_memory()
        ckpt.close()
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 - the record carries the cause
        import traceback

        traceback.print_exc()
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
    _write_result(args, name, rec)
    return 0 if rec["ok"] else 1


def _last_load_source():
    """``memory`` or ``storage``: what the engine's own CKPT_LOAD event
    of this process says."""
    from dlrover_tpu.observability import flight_recorder

    events = flight_recorder.recorder().snapshot(stacks=False)["events"]
    for event in reversed(events):
        if (event.get("name") == "trainer.ckpt.load"
                and "source" in (event.get("content") or {})):
            return event["content"]["source"]
    return "unknown"


def _record_compile(rec, jitscope):
    summary = jitscope.scope().summary()
    mine = [e for e in summary["recent"] if e["fn"] == "trainer.train_step"]
    rec["step_cache"] = [e["cache"] for e in mine]
    rec["step_cache_hits"] = sum(1 for e in mine if e["cache"] == "hit")
    rec["step_compile_s"] = round(sum(e["compile_s"] for e in mine), 2)
    rec["compile_s_all"] = summary["compile_s"]
    rec["cache_hits_all"] = summary["cache_hits"]
    rec["cache_misses_all"] = summary["cache_misses"]


def _native_in_use(get_timer, fastcopy):
    """Paths of the native libraries this process really mapped (from
    /proc/self/maps), or ``python-fallback``."""
    used = {
        "tpu_timer": get_timer().native, "fastcopy": fastcopy.available(),
    }
    mapped = {}
    with open("/proc/self/maps") as f:
        for line in f:
            for lib in used:
                if line.rstrip().endswith(f"lib{lib}.so"):
                    mapped[lib] = line.split()[-1]
    return {
        lib: (mapped.get(lib, "loaded-but-not-mapped") if on
              else "python-fallback")
        for lib, on in used.items()
    }


def worker_main(args):
    if args.worker == "train":
        return worker_train(args)
    fn = {"kernel": worker_kernel, "sync": worker_sync}[args.worker]
    try:
        rec = fn(args)
    except Exception as e:  # noqa: BLE001 - the record carries the cause
        import traceback

        traceback.print_exc()
        rec = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
    _write_result(args, f"{args.worker}.json", rec)
    return 0 if rec["ok"] else 1


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU, LlamaConfig.tiny(), reference attention")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default="")
    parser.add_argument("--worker", choices=("kernel", "train", "sync"),
                        default="")
    parser.add_argument("--tag", default="crash")
    parser.add_argument("--ckpt", default="")
    parser.add_argument("--crash-at", type=int, default=-1)
    args = parser.parse_args(argv)
    if args.worker:
        return worker_main(args)
    return orchestrate(args)


if __name__ == "__main__":
    sys.exit(main())
