"""The training script ``tpurun`` launches for the kill-and-resume job:
both incarnations.  It holds the chip; the orchestrator (``resume.py``)
never does."""

T_ENTRY = __import__("time").time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def _write(path, record):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f, default=str)
    os.replace(tmp, path)


def _last_load(flight_recorder):
    """(source, seconds) of this process's ``trainer.ckpt.load`` event."""
    events = flight_recorder.recorder().snapshot(stacks=False)["events"]
    for event in reversed(events):
        content = event.get("content") or {}
        if event.get("name") == "trainer.ckpt.load" and "source" in content:
            return content["source"], event
    return "unknown", None


def main(argv=None):  # noqa: C901 - one script, told in order
    parser = argparse.ArgumentParser()
    parser.add_argument("--spec", required=True)
    args = parser.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    config, traffic, rehearse = spec["config"], spec["traffic"], spec["rehearse"]

    import dlrover_tpu.trainer as trainer_pkg

    ctx = trainer_pkg.init()
    import flax.linen as nn
    import jax
    import numpy as np

    from benchmarks import common, program
    from benchmarks import trace as trace_mod

    restart = int(ctx.restart_count)
    rec = {"ok": False, "pid": os.getpid(), "restart": restart,
           "entry_ts": T_ENTRY, "phases": {}}
    path = os.path.join(spec["out"], f"r{min(restart, 1)}.json")
    try:
        device = common.device_record(jax)
        rec["device"] = device
        rec["boot_done_ts"] = time.time()
        if not rehearse and (device["platform"] != "tpu"
                             or device["count"] != spec["chips"]):
            raise RuntimeError(f"wanted {spec['chips']} tpu chip(s), "
                               f"JAX reports {device}")
        from dlrover_tpu.observability import flight_recorder, jitscope
        from dlrover_tpu.trainer.flash_checkpoint import (
            Checkpointer, StorageType,
        )

        tracing = bool(spec["trace"]) and restart > 0
        annotate = jax.profiler.TraceAnnotation
        span = None
        if tracing:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(spec["trace_dir"],
                                     profiler_options=options)
            span = annotate("bench.window")
            span.__enter__()

        t0 = time.time()
        with annotate("bench.build"):
            family, model, trainer = program.make_trainer(config, rehearse)
            pool = program.make_pool(config, rehearse, spec["seed"], family)
            key = program.make_key(spec["seed"])
            sample = pool[0]["input_ids"]
            ckpt = Checkpointer(spec["ckpt"])
            shardings = trainer.state_sharding_for(key, sample)
            abstract = trainer.abstract_state(key, sample)
        rec["phases"]["build_s"] = time.time() - t0
        t0 = time.time()
        with annotate("bench.load_checkpoint"):
            state, start_step = ckpt.load_checkpoint(abstract, shardings)
            if state is not None:
                jax.block_until_ready(state)
        rec["restore_s"] = time.time() - t0
        if state is None:
            t0 = time.time()
            state = program.make_state(
                trainer, family, config, rehearse, spec["seed"], pool)
            jax.block_until_ready(state)
            rec["phases"]["create_state_s"] = time.time() - t0
            start_step = 0
            rec["restore_source"] = "fresh"
        else:
            trainer.state_shardings = shardings
            rec["restore_source"], event = _last_load(flight_recorder)
            rec["restore_event"] = event
        rec["start_step"] = int(start_step)
        rec["params"] = sum(
            int(np.prod(x.shape))
            for x in jax.tree.leaves(nn.meta.unbox(state.params)))
        rec["state_bytes"] = int(sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(state)
            if hasattr(x, "dtype")))

        if restart == 0:
            # the plain reference, once, in set-up: part of ``correct``
            from benchmarks.jobs_shared import reference_check

            ok, detail = reference_check(
                config, rehearse, family, model, trainer, state, pool)
            rec["reference"] = {"ok": ok, **detail}

        losses, step_seconds = {}, {}
        rec.update(losses=losses, step_seconds=step_seconds)
        save_step = int(traffic["steps_before_save"])
        last_step = save_step + int(traffic["steps_after_save"])
        tier = getattr(StorageType, traffic["tier"])

        def one_step(step):
            nonlocal state
            t0 = time.time()
            with annotate("bench.shard_batch"):
                batch = trainer.shard_batch(pool[step % len(pool)])
            with annotate("bench.train_step"):
                state, metrics = trainer.train_step(state, batch)
            with annotate("bench.read_back"):
                loss = float(jax.device_get(metrics["loss"]))
            step_seconds[str(step)] = time.time() - t0
            # the bits of the loss, not its decimal rendering
            losses[str(step)] = float(loss).hex()
            if not np.isfinite(loss):
                raise RuntimeError(f"loss at step {step} is {loss}")
            return loss

        def note_compile():
            mine = [e for e in jitscope.scope().summary()["recent"]
                    if e["fn"] == "trainer.train_step"]
            rec["step_cache"] = [e["cache"] for e in mine]
            rec["step_compile_s"] = sum(e["compile_s"] for e in mine)

        if restart == 0:
            for step in range(1, save_step + 1):
                one_step(step)
                if step == 1:
                    note_compile()
            # training goes on beside the save, as in a job (waited for
            # with the loop idle it lands in twice the time): at least
            # ``steps_after_save`` steps, and on until it has landed
            import threading

            t0 = time.time()
            rec["save_blocked_s"] = ckpt.save_checkpoint(
                save_step, state, tier)
            box = {}

            def wait_landed():
                box["landed"] = bool(ckpt.wait_latest_checkpoint(
                    timeout=float(traffic["land_timeout_s"])))
                box["landed_s"] = time.time() - t0

            waiter = threading.Thread(target=wait_landed, daemon=True)
            waiter.start()
            step = save_step
            while step < last_step or waiter.is_alive():
                step += 1
                one_step(step)
            last_step = step
            if not box.get("landed"):
                raise RuntimeError("the save did not land")
            rec["save_landed_s"] = box["landed_s"]
            rec["saved_step"] = save_step
            rec["memory_peak_bytes"] = common.memory_peak_bytes(jax)
            rec["ok"] = True
            _write(path, rec)
            # a job that is killed is killed while it trains: keep stepping
            # until the orchestrator's SIGKILL (or, if it never comes, stop)
            give_up = time.time() + 120
            step = last_step
            while time.time() < give_up:
                step += 1
                one_step(step)
            return 9

        steps = list(range(start_step + 1, last_step + 1))
        rec["steps_after_restore"] = steps
        for i, step in enumerate(steps):
            one_step(step)
            if i == 0:
                rec["first_step_done_ts"] = time.time()
                note_compile()
        rest = [step_seconds[str(s)] for s in steps[1:]]
        rec["step_s_after_first"] = (sorted(rest)[len(rest) // 2]
                                     if rest else None)
        if tracing:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            found = trace_mod.find_xplane(spec["trace_dir"])
            rec["trace"] = (trace_mod.reduce(trace_mod.load(found))
                            if found else {})
        rec["memory_peak_bytes"] = common.memory_peak_bytes(jax)
        ckpt.engine.unlink_memory()
        ckpt.close()
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 - the record carries the cause
        traceback.print_exc()
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
    _write(path, rec)
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
