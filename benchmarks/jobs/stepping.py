"""The stepping job: ``Trainer`` steps for the length of the window, in
this process, with at most one asynchronous save beside them (cells
``*.steady`` and ``*.save_mem``).

The loop is the one a real job runs: it keeps one step in flight (dispatch
step i+1, then read back the loss of step i, as a job that logs
asynchronously does), so the measurement does not leave the device idle,
and the time between two read-backs is the step time.  A fresh host batch
goes through ``Trainer.shard_batch`` every step.

Set-up, in order: environment and native libraries, JAX and the device
check (the call in which the runtime claims the chip is timed apart and is
not in ``setup_s``), model and state on the device from the seed, the
agreement with the plain reference, the warm-up steps (the first
compiles), and in a save cell one warm-up save that lands and is held to
the live state bit for bit.
Nothing compiles inside the window; if something does, ``correct`` is
false.
"""

import os
import threading
import time

import numpy as np

from benchmarks import common, program
from benchmarks import trace as trace_mod
from benchmarks.jobs_shared import reference_check


class SnapshotProbe:
    """A seeded sample of the state: from each of a few leaves one run of
    consecutive elements along the last axis, copied on the device when a
    save is called (a dispatch, no transfer) and held, after the snapshot
    has landed, to the same bytes read from shm on the host: bit for bit.
    No whole-state transfer."""

    def __init__(self, state, seed, n_leaves, n_elems):
        from dlrover_tpu.trainer.flash_checkpoint import snapshot

        self._plan = snapshot.plan_shards
        rng = np.random.default_rng(seed)
        leaves = [leaf for leaf in self._plan(state) if leaf["gshape"]]
        picks = rng.choice(len(leaves), size=min(n_leaves, len(leaves)),
                           replace=False)
        self.sample = []
        for i in sorted(int(p) for p in picks):
            leaf = leaves[i]
            shard_no = int(rng.integers(len(leaf["shards"])))
            shape = tuple(leaf["shards"][shard_no]["data"].shape)
            n = min(n_elems, shape[-1])
            lead = tuple(int(rng.integers(d)) for d in shape[:-1])
            first = int(rng.integers(shape[-1] - n + 1))
            self.sample.append({
                "path": leaf["path"], "shard": shard_no, "lead": lead,
                "first": first, "elems": n,
                "offset_elems": int(np.ravel_multi_index(
                    lead + (first,), shape)),
            })

    def capture(self, state):
        import jax.numpy as jnp

        by_path = {leaf["path"]: leaf for leaf in self._plan(state)}
        return [
            jnp.copy(by_path[s["path"]]["shards"][s["shard"]]["data"][
                s["lead"] + (slice(s["first"], s["first"] + s["elems"]),)])
            for s in self.sample
        ]

    def compare(self, captured, shm_segment, want_step):
        """(ok, detail): the landed snapshot is of ``want_step`` and holds
        the captured bytes.  The segment is attached anew each time: the
        engine makes a new one under the same name when the size moves."""
        from dlrover_tpu.common.multi_process import SharedMemoryBuffer
        from dlrover_tpu.trainer.flash_checkpoint import snapshot

        shm_buffer = SharedMemoryBuffer(shm_segment)
        meta = snapshot.read_snapshot_meta(shm_buffer)
        if meta is None:
            return False, {"error": "no committed snapshot in shm"}
        detail = {"snapshot_step": meta["step"], "want_step": want_step,
                  "leaves_compared": 0, "bytes_compared": 0, "unequal": []}
        if meta["step"] != want_step:
            return False, detail
        base = snapshot.payload_base(shm_buffer)
        by_path = {leaf["path"]: leaf for leaf in meta["leaves"]}
        for s, dev in zip(self.sample, captured):
            live = np.asarray(dev).tobytes()
            shard = by_path[s["path"]]["shards"][s["shard"]]
            itemsize = len(live) // s["elems"]
            start = base + shard["offset"] + s["offset_elems"] * itemsize
            in_shm = bytes(shm_buffer.buf[start: start + len(live)])
            detail["leaves_compared"] += 1
            detail["bytes_compared"] += len(live)
            if in_shm != live:
                detail["unequal"].append(s["path"])
        return not detail["unequal"], detail


def run(run):
    """The job; whatever happens, the snapshot segment it made in
    ``/dev/shm`` is gone when it returns."""
    holder = {}
    try:
        return _run(run, holder)
    finally:
        ckpt = holder.get("ckpt")
        if ckpt is not None:
            ckpt.engine.unlink_memory()
            ckpt.close()


def _run(run, holder):  # noqa: C901 - one job, told in order
    t_env = t_phase = run.begin("env")
    os.environ.update(run.program_env())
    how, libs = common.build_native()
    traffic = run.traffic
    batch_size, seq = program.sizes(run.config, run.rehearse)
    run.emit({"phase": "env", "ok": True, "native_build": how,
              "seconds": round(time.time() - t_phase, 2)})

    t_phase = run.begin("device")
    import dlrover_tpu.trainer as trainer_pkg

    trainer_pkg.init()
    t_program = time.time()
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.common import fastcopy
    from dlrover_tpu.observability import flight_recorder, jitscope
    from dlrover_tpu.trainer.bootstrap import compile_cache_info
    from dlrover_tpu.trainer.flash_checkpoint import Checkpointer, StorageType
    from dlrover_tpu.trainer.flash_checkpoint.engine import shm_name

    # the first ``jax.devices()``: the runtime makes its client and claims
    # the chip here (a program that touched the backend earlier, in its
    # ``init()``, would put those seconds into ``setup_s``, where they show)
    t_imports = time.time()
    device = common.device_record(jax)
    runtime_init_s = time.time() - t_imports
    common.require_chips(run, device)
    compiles = common.CompileWatch()
    peaks = None if run.rehearse else common.peaks_for(device["kind"])
    run.emit({"phase": "device", "ok": True, **device,
              "cache": compile_cache_info(),
              "before_job_s": round(t_env - run.t_process_start, 3),
              "program_import_s": round(t_program - t_phase, 3),
              "library_import_s": round(t_imports - t_program, 3),
              "runtime_init_s": round(runtime_init_s, 3),
              "seconds": round(time.time() - t_phase, 2)})

    # -- model, data and state, all from the seed -------------------------
    t_phase = run.begin("state")
    family, model, trainer = program.make_trainer(run.config, run.rehearse)
    mesh = trainer.mesh
    pool = program.make_pool(run.config, run.rehearse, run.seed, family)
    state = program.make_state(
        trainer, family, run.config, run.rehearse, run.seed, pool)
    jax.block_until_ready(state)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(nn.meta.unbox(state.params)))
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state) if hasattr(x, "dtype"))
    run.emit({"phase": "state", "ok": True, "params": n_params,
              "state_bytes": int(state_bytes), "batch": batch_size, "seq": seq,
              "mesh": {k: int(v) for k, v in mesh.shape.items()},
              "seconds": round(time.time() - t_phase, 2)})

    t_phase = run.begin("reference")
    ref_ok, ref = reference_check(
        run.config, run.rehearse, family, model, trainer, state, pool)
    run.emit({"phase": "reference", "ok": ref_ok, **ref,
              "seconds": round(time.time() - t_phase, 2)})

    # -- the loop ----------------------------------------------------------
    annotate = jax.profiler.TraceAnnotation
    step_no = 0           # steps dispatched since the state was made
    pending = None        # (step number, its loss still on the device)
    losses_bad = 0

    def dispatch():
        nonlocal state, step_no
        with annotate("bench.shard_batch"):
            batch = trainer.shard_batch(pool[step_no % len(pool)])
        with annotate("bench.train_step"):
            state, metrics = trainer.train_step(state, batch)
        step_no += 1
        return step_no, metrics["loss"]

    def read_back(item):
        nonlocal losses_bad
        with annotate("bench.read_back"):
            loss = float(jax.device_get(item[1]))
        if not np.isfinite(loss):
            losses_bad += 1
        return loss

    def drain():
        nonlocal pending
        loss = None
        if pending is not None:
            loss = read_back(pending)
            pending = None
        return loss

    t_phase = run.begin("warmup")
    first_loss = None
    for _ in range(int(traffic["warmup_steps"])):
        nxt = dispatch()
        if pending is not None:
            loss = read_back(pending)
            first_loss = loss if first_loss is None else first_loss
        pending = nxt
    last_loss = drain()
    run.emit({"phase": "warmup", "ok": True, "steps": step_no,
              "first_loss": first_loss, "last_loss": last_loss,
              "jitscope": {k: jitscope.scope().summary()[k] for k in
                           ("compile_s", "cache_hits", "cache_misses")},
              "seconds": round(time.time() - t_phase, 2)})

    saves = int(traffic.get("saves_per_window", 0))
    ckpt = probe = shm_segment = None
    warm_save = {}
    if saves:
        t_phase = run.begin("warmup_save")
        scope = f"bench{os.getpid()}"
        ckpt = Checkpointer(os.path.join(run.scratch, "ckpt"), scope=scope)
        holder["ckpt"] = ckpt
        shm_segment = shm_name(ckpt.engine.process_id, scope)
        tier = getattr(StorageType, traffic["tier"])
        probe = SnapshotProbe(state, run.seed, int(traffic["sample_leaves"]),
                              int(traffic["sample_elems"]))
        if traffic.get("warmup_save"):
            # steps go on beside it, as beside the window's save: waited
            # for with the loop idle it takes twice as long to land (25.5-
            # 27.4 s against 10.5-13.9 s, my chip runs, PR 24)
            captured = probe.capture(state)
            t0 = time.time()
            saved_step = step_no
            blocked = ckpt.save_checkpoint(saved_step, state, tier)
            box = {}

            def wait_warm():
                box["landed"] = bool(ckpt.wait_latest_checkpoint(
                    timeout=float(traffic["land_timeout_s"])))
                box["landed_s"] = time.time() - t0

            waiter = threading.Thread(target=wait_warm, daemon=True)
            waiter.start()
            while waiter.is_alive():
                nxt = dispatch()
                drain()
                pending = nxt
            drain()
            same, detail = probe.compare(captured, shm_segment, saved_step)
            warm_save = {"landed": box.get("landed", False), "bit_equal": same,
                         "blocked_s": blocked,
                         "landed_s": box.get("landed_s"), **detail}
            for _ in range(int(traffic["steps_after_warmup_save"])):
                nxt = dispatch()
                drain()
                pending = nxt
            drain()
        run.emit({"phase": "warmup_save", "ok": bool(
            not warm_save or (warm_save["landed"] and warm_save["bit_equal"])),
            **warm_save, "native": common.native_in_use(libs),
            "fastcopy": bool(fastcopy.available()),
            "seconds": round(time.time() - t_phase, 2)})

    # -- the window --------------------------------------------------------
    run.begin("window")
    tracing = {"on": False, "dir": os.path.join(run.scratch, "trace"),
               "span": None, "from": None, "steps": 0}
    if run.trace:
        tracing["from"] = int(traffic["trace_from_window_step"])
        tracing["steps"] = int(traffic["trace_steps"])
        tracing["min_steps"] = int(traffic["trace_min_steps"])
        tracing["max_seconds"] = float(traffic["trace_max_seconds"])
    save_at = int(traffic["save_at_window_step"]) if saves else None
    save = {}
    compile_before = compiles.snapshot()
    events_before = len(
        flight_recorder.recorder().snapshot(stacks=False)["events"])
    # set-up is the process's start to the window's opening less the one
    # call in which the runtime makes its client and claims the chip: half
    # of a 21 s set-up, 6.5-15 s from one machine and hour to the next, and
    # nothing of the benchmark's or the program's (PERF.md section 2)
    setup_wall_s = time.time() - run.t_process_start
    setup_s = setup_wall_s - runtime_init_s
    done_at = []          # perf_counter at each read-back inside the window
    window_first_step = step_no
    t_open = time.perf_counter()
    deadline = t_open + run.seconds
    started = 0
    while True:
        k = step_no - window_first_step      # window step about to start
        if run.trace and k == tracing["from"] and not tracing["on"]:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(tracing["dir"], profiler_options=options)
            tracing["on"] = True
            tracing["t0"] = time.perf_counter()
            tracing["span"] = annotate("bench.window")
            tracing["span"].__enter__()
        nxt = dispatch()
        started += 1
        if saves and k == save_at and not save:
            with annotate("bench.save_checkpoint"):
                captured = probe.capture(state)
                save["called_at"] = time.perf_counter()
                save["step"] = step_no
                save["blocked_s"] = ckpt.save_checkpoint(step_no, state, tier)
            save["captured"] = captured
            save["after_completions"] = len(done_at)

            def watch(box=save):
                box["landed"] = bool(ckpt.wait_latest_checkpoint(
                    timeout=float(traffic["land_timeout_s"])))
                box["landed_at"] = time.perf_counter()

            save["watcher"] = threading.Thread(target=watch, daemon=True)
            save["watcher"].start()
        if pending is not None:
            read_back(pending)
            now = time.perf_counter()
            if now > deadline:
                pending = nxt
                break
            done_at.append(now)
        pending = nxt
        if tracing["on"] and (
                k + 1 >= tracing["from"] + tracing["steps"]
                or (k + 1 >= tracing["from"] + tracing["min_steps"]
                    and time.perf_counter() - tracing["t0"]
                    >= tracing["max_seconds"])):
            with annotate("bench.trace_drain"):
                jax.block_until_ready(pending[1])
            tracing["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing["on"] = False
            tracing["from"] = None
    window_closed = time.perf_counter()
    compile_after = compiles.snapshot()
    # outside the window: the step still in flight, an unfinished trace, the
    # landing of the save
    drain()
    if tracing["on"]:
        tracing["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    run.begin("after_window")
    save_landed = None
    if save:
        save["watcher"].join(float(traffic["land_timeout_s"]) + 5)
        save_landed = bool(save.get("landed"))
    events = flight_recorder.recorder().snapshot(stacks=False)["events"]
    new_events = events[events_before:] if len(events) >= events_before else events
    fallbacks = [e for e in events
                 if e.get("name") == "trainer.ckpt.sync_fallback"]

    # -- the numbers -------------------------------------------------------
    intervals = [b - a for a, b in zip([t_open] + done_at[:-1], done_at)]
    values = {"setup_s": setup_s, "setup_wall_s": setup_wall_s}
    tokens_per_step = batch_size * seq
    # the window ends at the last step completed inside ``--seconds``: all
    # the steps and all the time up to there, and no step cut in two (a
    # nominal end would make the rate move in quanta of one step)
    window_s = done_at[-1] - t_open
    values["tokens_per_s"] = len(done_at) * tokens_per_step / window_s
    values["step_p95_ms"] = 1e3 * common.quantile(intervals, 0.95)
    values["step_ms"] = 1e3 * common.quantile(intervals, 0.5)
    peak = common.memory_peak_bytes(jax)
    if peak is not None:
        values["hbm_peak_gib"] = peak / 2 ** 30
    flops_per_token = family.flops_per_token(run.config, seq, run.rehearse)
    if peaks is not None:
        values["mfu_pct"] = 100.0 * values["tokens_per_s"] * flops_per_token / (
            run.chips * peaks["bf16_flops_per_s"])
    save_ok, save_detail = True, {}
    if save:
        n0 = save["after_completions"]
        base = intervals[max(0, n0 - int(traffic["baseline_steps"])): n0]
        base_med = common.quantile(base, 0.5)
        values["save_cost_s.layer"] = sum(t - base_med for t in intervals[n0:])
        values["save_blocked_ms"] = 1e3 * float(save["blocked_s"])
        if save_landed:
            values["save_landed_s"] = save["landed_at"] - save["called_at"]
        same, save_detail = (probe.compare(
            save["captured"], shm_segment, save["step"])
            if save_landed else (False, {"error": "the save never landed"}))
        save_events = [e["content"] for e in new_events
                       if e.get("name") == "trainer.ckpt.save"]
        save_detail.update(
            baseline_step_ms=1e3 * base_med, baseline_steps=len(base),
            steps_after_save=len(intervals) - n0, events=save_events,
            landed_after_window_s=(
                save["landed_at"] - window_closed if save_landed else None),
        )
        save_ok = bool(
            save_landed and same and not fallbacks
            and save["blocked_s"] >= 0
            and any(e.get("async") for e in save_events))
    compiled = common.CompileWatch.between(compile_before, compile_after)
    no_compile = compiled["stepping"]["events"] == 0
    if save:
        values["stager_compile_s"] = compiled["other"]["seconds"]
    warm_ok = bool(not warm_save
                   or (warm_save["landed"] and warm_save["bit_equal"]))
    correct = bool(ref_ok and no_compile and save_ok and warm_ok
                   and losses_bad == 0)
    run.emit({
        "phase": "window", "ok": correct, "seconds": run.seconds,
        "window_s": window_s,
        "steps_completed": len(done_at), "steps_started": started,
        "tokens_per_step": tokens_per_step,
        "step_samples": len(intervals),
        "samples_beyond_p95": int(0.05 * len(intervals)),
        "step_ms_min_med_p95_max": [
            1e3 * min(intervals), values["step_ms"], values["step_p95_ms"],
            1e3 * max(intervals)],
        "flops_per_token": flops_per_token,
        "compiled_in_window": compiled,
        "non_finite_losses": losses_bad, "sync_fallbacks": len(fallbacks),
        "reference_ok": ref_ok, "save": save_detail,
        "values": {k: v for k, v in values.items()},
    })

    # every number ``correct`` compared, beside its limit
    checks = {
        "token_max_abs_err": [ref["token_max_abs_err"], ref["token_atol"]],
        "mean_abs_err": [ref["mean_abs_err"], ref["mean_atol"]],
        "compiles_in_window": [compiled["stepping"]["events"], 0],
        "non_finite_losses": [losses_bad, 0],
    }
    if "token_median_abs_err" in ref:
        checks["token_median_abs_err"] = [
            ref["token_median_abs_err"], ref["median_atol"]]
    if ref.get("low_margin_share_by_layer"):
        checks["low_margin_share"] = [
            max(ref["low_margin_share_by_layer"]), ref["low_margin_share_max"]]
    if warm_save:
        checks["warmup_save_unequal_leaves"] = [
            len(warm_save.get("unequal", [])) if warm_save["landed"] else -1, 0]
    if save:
        checks["save_unequal_leaves"] = [
            len(save_detail.get("unequal", [])) if save_landed else -1, 0]
        checks["sync_fallbacks"] = [len(fallbacks), 0]
    observed = {
        "values": values, "correct": correct, "checks": checks,
        "attempted": started + (1 if save else 0),
        "failed": losses_bad + (1 if save and not save_landed else 0),
        "device": {**device, "memory_peak_bytes": peak},
        "config": run.config, "batch": batch_size, "seq": seq,
        "chips": run.chips, "peaks": peaks, "family": family,
    }
    if run.trace:
        run.begin("trace")
        path = trace_mod.find_xplane(tracing["dir"])
        loaded = trace_mod.load(path) if path else None
        reduced = trace_mod.reduce(loaded) if loaded else {}
        observed["trace"] = reduced
        observed["trace_loaded"] = loaded
        if "busy_s" in reduced:
            observed["device"]["busy_s"] = reduced["busy_s"]
            observed["device"]["window_s"] = reduced["window_s"]
            observed["breakdown"] = {"device_ops": reduced["device_ops"],
                                     "idle_gaps": reduced["idle_gaps"]}
        run.emit({"phase": "trace", "ok": "busy_s" in reduced,
                  "xplane_bytes": os.path.getsize(path) if path else 0,
                  **{k: v for k, v in reduced.items()}})

    return observed
