"""Benchmark entry: prints ONE JSON line for the driver.

Primary metric (BASELINE.md): Flash-Checkpoint blocking save seconds at a
GPT-1.5B-class model — the reference's headline is 151s -> 0.5s blocking
(docs/blogs/megatron_flash_checkpoint.md:157-160).  ``vs_baseline`` is
reference_blocking / ours (>1 = faster than the reference's own number).
Until the flash-checkpoint stage lands, falls back to reporting training
throughput with a neutral vs_baseline.

Run on the real TPU chip: without one it fails, unless the smoke preset
is asked for by name (DLROVER_TPU_BENCH_PRESET=tiny, CPU).
"""

import json
import os
import subprocess
import sys
import time


#: bf16 peak FLOP/s of one chip by ``device_kind`` (Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s).  A device that is not here is
#: an error, never a default.
_PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def _peak_bf16_flops(device_kind: str) -> float:
    try:
        return _PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published bf16 peak recorded for device_kind "
            f"{device_kind!r}; add it to bench._PEAK_BF16_FLOPS with its "
            "source"
        ) from None


def _model_and_batch(preset: str):
    import jax.numpy as jnp  # noqa: F401 - jax must import before models
    import numpy as np

    from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if preset == "tiny":
        cfg = LlamaConfig.tiny()
        B, S = 8, 64
    else:
        # 1.24B-param Llama (GPT-1.5B-class — the reference's bench point,
        # megatron_flash_checkpoint.md:157): fp32 masters + bf16 Adam
        # moments + bf16 grads fit one 16GB v5e chip.
        # attention_impl="flash": the Pallas FA2 kernel is the production
        # path, numerically validated on-device by tests_tpu/.
        cfg = LlamaConfig.llama2_1b(
            max_seq_len=2048, attention_impl="flash"
        )
        B, S = 4, 2048
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(B, S + 1))
    batch = {
        "input_ids": np.asarray(ids[:, :-1], np.int32),
        "labels": np.asarray(ids[:, 1:], np.int32),
    }
    return model, cfg, batch


def bench_throughput(preset: str) -> dict:
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.optim import create_optimizer
    from dlrover_tpu.trainer.train import Trainer

    model, cfg, batch = _model_and_batch(preset)
    ndev = jax.device_count()
    mesh = build_mesh(MeshConfig(dp=ndev, fsdp=1, tp=1))
    opt = create_optimizer(
        peak_lr=3e-4, warmup_steps=10, total_steps=10_000,
        moment_dtype=jnp.bfloat16,
    )
    trainer = Trainer(
        model, opt, mesh, grads_dtype=jnp.bfloat16
    )
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    # warm up / compile.  hard_block (utils/timing.py): the step is only
    # done once a value read back from the device says so
    from dlrover_tpu.utils.timing import hard_block

    state, m = trainer.train_step(state, batch)
    hard_block(m["loss"])
    steps = 3 if preset == "tiny" else 15
    t0 = time.time()
    for _ in range(steps):
        state, m = trainer.train_step(state, batch)
    hard_block(m["loss"])
    dt = (time.time() - t0) / steps
    B, S = batch["input_ids"].shape
    tokens_per_sec = B * S / dt
    n_params = model.num_params()
    # standard MFU accounting (PaLM appendix B, causal variant): matmul
    # FLOPs 6N per token plus causal self-attention 12*L*h*S/2 = 6*L*h*S
    # per token.  Remat recompute is NOT counted (it is overhead, not
    # useful work), which keeps the number conservative.
    L, h = cfg.num_layers, cfg.num_heads * cfg.head_dim
    flops_per_step = (6 * n_params + 6 * L * h * S) * B * S
    mfu = None  # the tiny preset runs on the CPU: not a device metric
    if preset != "tiny":
        peak = _peak_bf16_flops(jax.devices()[0].device_kind) * ndev
        mfu = round((flops_per_step / dt) / peak, 4)
    return {
        "tokens_per_sec": round(tokens_per_sec),
        "step_ms": round(dt * 1000, 1),
        "mfu": mfu,
        "mfu_formula": "(6N + 6*L*h*S)*tokens / peak; remat not counted",
        "params": n_params,
        "attention_impl": cfg.attention_impl,
        "optimizer": "adamw(bf16 moments), bf16 grads, fp32 masters",
        "sync": "hard_block",
        # single-chip dp=ndev mesh: non-exact policies only engage at
        # dp>1 (the grad_sync drill below measures them on a CPU mesh)
        "grad_sync": "exact",
    }


def _grad_sync_evidence(timeout: float = 600.0) -> dict:
    """Per-mode grad-sync step time + estimated dp bytes-on-wire
    (exact vs int8-quantized), measured in a subprocess on a virtual
    4-device CPU mesh (``parallel/grad_sync_bench.py``).  Subprocess so
    the forced CPU backend never collides with this process's TPU
    session."""
    prefix = "GRAD_SYNC_BENCH "
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.parallel.grad_sync_bench"],
            capture_output=True, timeout=timeout, text=True,
            cwd=os.path.dirname(__file__) or ".",
        )
        for line in proc.stdout.splitlines():
            if line.startswith(prefix):
                return json.loads(line[len(prefix):])
        return {"error": (proc.stderr or proc.stdout)[-400:]}
    except (subprocess.TimeoutExpired, OSError, ValueError) as e:
        return {"error": str(e)[:400]}


def _dist_ckpt_evidence(timeout: float = 600.0) -> dict:
    """Distributed-commit persist bench: GB/s vs simulated host count,
    differential bytes-written-per-step, partial-read bytes vs the
    full-read baseline.  Subprocess so the forced platform never
    collides with this process's backend."""
    prefix = "DIST_CKPT_BENCH "
    mb = os.getenv("DLROVER_TPU_BENCH_DIST_CKPT_MB", "64")
    try:
        proc = subprocess.run(
            [sys.executable, "-m",
             "dlrover_tpu.trainer.flash_checkpoint.dist_bench",
             "--mb", mb],
            capture_output=True, timeout=timeout, text=True,
            cwd=os.path.dirname(__file__) or ".",
        )
        for line in proc.stdout.splitlines():
            if line.startswith(prefix):
                return json.loads(line[len(prefix):])
        return {"error": (proc.stderr or proc.stdout)[-400:]}
    except (subprocess.TimeoutExpired, OSError, ValueError) as e:
        return {"error": str(e)[:400]}


def _tier1_dots() -> int:
    """Tier-1 dot count for the history entry: the driver can pass it
    (DLROVER_TPU_BENCH_TIER1_DOTS), else the ROADMAP verify command's
    tee'd log is parsed when present; -1 = unknown."""
    try:
        explicit = int(os.getenv("DLROVER_TPU_BENCH_TIER1_DOTS", "-1"))
    except ValueError:  # e.g. exported as "" to unset it
        explicit = -1
    if explicit >= 0:
        return explicit
    try:
        import re

        with open("/tmp/_t1.log", "rb") as f:
            text = f.read().decode("utf-8", errors="replace")
        dots = 0
        for line in text.splitlines():
            if re.fullmatch(r"[.FEsx]+( *\[ *[0-9]+%\])?", line.strip()):
                dots += line.count(".")
        return dots
    except OSError:
        return -1


def _history_path() -> str:
    return os.getenv("DLROVER_TPU_BENCH_HISTORY", "") or os.path.join(
        os.path.dirname(__file__) or ".", "BENCH_history.jsonl"
    )


def _history_entry(result: dict, preset: str) -> dict:
    """One machine-readable BENCH_history.jsonl round: the queryable
    perf trajectory the regression sentinel (and humans) read.  Flat
    keys so `jq`/the gate never chase nested paths."""
    detail = result.get("detail", {})
    entry = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "epoch": round(time.time(), 1),
        "metric": result.get("metric"),
        "value": result.get("value"),
        "unit": result.get("unit"),
        "vs_baseline": result.get("vs_baseline"),
        "preset": preset,
        "tpu_unavailable": bool(detail.get("tpu_unavailable")),
        "tier1_dots": _tier1_dots(),
    }
    if result.get("unit") == "s":
        entry["blocking_save_s"] = result.get("value")
    for key in ("step_ms", "tokens_per_sec", "mfu"):
        if detail.get(key) is not None:
            entry[key] = detail[key]
    # gate-watched r22 columns: the live in-place transition's ledger
    # price creeping UP, or its edge over the restart path shrinking
    # DOWN, is a regression in the headline elasticity win
    for key in ("live_reshard_s", "reshard_speedup_vs_restart"):
        if isinstance(detail.get(key), (int, float)):
            entry[key] = detail[key]
    # gate-watched r24 columns: a failed node's peer-replicated restore
    # slowing DOWN (mttr up) or the peer transfer rate dropping means
    # the sub-minute recovery headline is eroding
    recovery = detail.get("peer_recovery") or {}
    for key in ("recovery_mttr_s", "peer_read_gbps"):
        if isinstance(recovery.get(key), (int, float)):
            entry[key] = recovery[key]
    probe = detail.get("tpu_probe")
    if probe:
        entry["tpu_probe"] = {
            "ok": probe.get("ok"), "attempts": probe.get("attempts"),
            **({"last_error": probe["last_error"]}
               if probe.get("last_error") else {}),
        }
    goodput = detail.get("goodput") or {}
    for key in ("training_goodput", "goodput"):
        if isinstance(goodput.get(key), (int, float)):
            entry[f"drill_{key}"] = goodput[key]
    recorder = detail.get("flight_recorder") or {}
    if recorder.get("pct_of_step") is not None:
        entry["recorder_pct_of_step"] = recorder["pct_of_step"]
    ledger = detail.get("goodput_ledger") or {}
    if ledger:
        entry["goodput_ledger"] = {
            "goodput": ledger.get("goodput"),
            "dominant": ledger.get("dominant"),
            "phases": ledger.get("phases"),
        }
        # gate-watched r25 column: the wall-share the ledger booked to
        # blocking shard waits — creeping UP means the input pipeline
        # is eating step time the accelerators should be getting
        phases = ledger.get("phases") or {}
        wall = ledger.get("wall_s")
        if (isinstance(phases.get("input_starved"), (int, float))
                and isinstance(wall, (int, float)) and wall > 0):
            entry["gp_input_starved"] = round(
                phases["input_starved"] / wall, 6
            )
    # gate-watched r25 columns from the fleet leg's longpoll mode: the
    # master's shard-lease p99 creeping UP, or fleet-wide shard
    # throughput dropping DOWN, is the data plane regressing
    fleet = detail.get("fleet_bench") or {}
    longpoll = (fleet.get("modes") or {}).get("longpoll") or {}
    if isinstance(longpoll.get("lease_p99_ms"), (int, float)):
        entry["data_p99_ms"] = longpoll["lease_p99_ms"]
    if isinstance(longpoll.get("shards_per_s"), (int, float)):
        entry["shards_per_s"] = longpoll["shards_per_s"]
    mem = detail.get("mem_account") or {}
    if mem and "error" not in mem:
        entry["mem_account"] = {
            "used_b": mem.get("used_b"),
            "headroom_b": mem.get("headroom_b"),
            "host_rss_b": mem.get("host_rss_b"),
            "subsystems": mem.get("subsystems"),
            "account_ok": mem.get("account_ok"),
        }
    brain = detail.get("brain_bench") or {}
    if isinstance(brain.get("fleet_goodput_gain"), (int, float)):
        # gate-watched column: Brain-on's aggregate fleet goodput
        # advantage over static allocation regressing DOWN means the
        # arbiter stopped earning its keep
        entry["fleet_goodput_gain"] = brain["fleet_goodput_gain"]
        entry["brain_bench"] = {
            "weighted_goodput_gain": brain.get("weighted_goodput_gain"),
            "decisions": (
                brain.get("modes", {}).get("brain", {})
                .get("decision_counts")
            ),
            "problems": (
                brain.get("assertions", {}).get("problems")
            ),
        }
    comp = detail.get("compile_observatory") or {}
    if comp and "error" not in comp:
        # flat gate-watched columns (compile_s up / cache_hit_ratio
        # down = regression) + the compact account
        if isinstance(comp.get("compile_s"), (int, float)):
            entry["compile_s"] = comp["compile_s"]
        if isinstance(comp.get("cache_hit_ratio"), (int, float)):
            entry["cache_hit_ratio"] = comp["cache_hit_ratio"]
        entry["compile_observatory"] = {
            "events": comp.get("events"),
            "by_trigger": comp.get("by_trigger"),
            "cache_hits": comp.get("cache_hits"),
            "cache_misses": comp.get("cache_misses"),
            "stalls": comp.get("stalls"),
        }
    return entry


def _read_history(path: str) -> list:
    entries = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    entries.append(json.loads(line))
                except ValueError:
                    continue  # half-written tail of a crashed round
    except OSError:
        pass
    return entries


def _history_and_gate(result: dict, preset: str) -> bool:
    """Append this round to BENCH_history.jsonl and judge it against
    the recorded trajectory with the sentinel's detector.  Returns True
    when the hard gate (DLROVER_TPU_BENCH_REGRESSION_GATE=1) should
    fail the bench; the verdict always rides the JSON + stderr."""
    gate_failed = False
    try:
        # EVERYTHING here is best-effort: the bench's one JSON line
        # must print no matter how the history/gate path fails
        path = _history_path()
        entry = _history_entry(result, preset)
        prior = _read_history(path)
    except Exception as e:  # noqa: BLE001 - the gate must not kill
        result.setdefault("detail", {})["regression_gate"] = {
            "error": str(e)[:300]
        }
        return False
    try:
        from dlrover_tpu.observability import sentinel

        verdict = sentinel.compare_round(prior, entry)
        result.setdefault("detail", {})["regression_gate"] = verdict
        if not verdict["ok"]:
            print(
                "bench: PERF REGRESSION vs recorded trajectory: "
                + json.dumps(verdict["checked"]),
                file=sys.stderr, flush=True,
            )
            gate_failed = os.getenv(
                "DLROVER_TPU_BENCH_REGRESSION_GATE", ""
            ) == "1"
        entry["regression_gate"] = {
            "ok": verdict["ok"],
            "regressions": verdict["regressions"],
        }
    except Exception as e:  # noqa: BLE001 - the gate must not kill
        result.setdefault("detail", {})["regression_gate"] = {
            "error": str(e)[:300]
        }
    try:
        with open(path, "a") as f:
            f.write(json.dumps(entry) + "\n")
    except OSError as e:
        print(f"bench: history append failed: {e}", file=sys.stderr,
              flush=True)
    return gate_failed


def main():
    preset = os.getenv("DLROVER_TPU_BENCH_PRESET", "default")
    model_tag = "llama-tiny" if preset == "tiny" else "llama-1.2B"
    on_device_recovery = None
    if preset == "tiny":
        # the smoke preset, asked for by name: the CPU, and only then
        import jax

        jax.config.update("jax_platforms", "cpu")
    else:
        # BEFORE any in-process jax use: the chip grants exclusive
        # per-process access, so the on-device recovery drill (worker
        # restart + compile-cache reload + shm restore on the real
        # backend — the <60s north-star is a hardware number) must own
        # the chip while this process has not initialized it yet.  Its
        # workers are pinned to the TPU, and a drill that fails fails
        # the bench: there is no CPU stand-in for a hardware number.
        from dlrover_tpu.trainer.flash_checkpoint.bench import (
            recovery_drill,
        )

        on_device_recovery = recovery_drill(timeout=600.0, platform="tpu")
        if "error" in on_device_recovery:
            sys.exit(
                "bench: on-device recovery drill failed: "
                + str(on_device_recovery["error"])
            )
        import jax

        if jax.default_backend() != "tpu":
            sys.exit(
                f"bench: default backend is {jax.default_backend()!r}, "
                "not tpu; only DLROVER_TPU_BENCH_PRESET=tiny runs "
                "without a chip"
            )
    fa_entry = None
    if preset != "tiny":
        # tune the flash-attention blocks for the bench shape FIRST so
        # the throughput run uses the measured-best kernel config
        try:
            from dlrover_tpu.ops.pallas import tuning

            # tune at the BENCH shape (batch included): block rankings
            # shift with grid occupancy, so tuning a different batch
            # could persist a winner that loses at the measured shape.
            # Reuse an existing trusted entry (hard_block-timed, same
            # shape, same chip model) — a 16-candidate fwd+bwd sweep
            # costs minutes per run.
            existing = tuning.trusted_entry(
                2048, 128, shape=[4, 2048, 16, 128]
            )
            if existing:
                fa_entry = dict(existing, reused=True)
            else:
                fa_entry = tuning.autotune(
                    seq_len=2048, head_dim=128, heads=16, batch=4
                )
        except Exception as e:  # noqa: BLE001 - tuning is best-effort
            fa_entry = {"error": str(e)[:200]}
    # graceful degradation: the bench must ALWAYS print its JSON line.
    # Each stage falls back independently (a 1.24B OOM in the
    # throughput stage must not void the checkpoint numbers, and vice
    # versa); errors are carried in the detail instead of crashing.
    result = None
    try:
        from dlrover_tpu.trainer.flash_checkpoint import bench as ckpt_bench

        result = ckpt_bench.run(preset)
    except Exception as e:  # noqa: BLE001 - OOM/backend failures
        print(f"bench: ckpt stage failed: {e}", file=sys.stderr, flush=True)
        result = {
            "metric": f"train_tokens_per_sec ({model_tag}, single chip)",
            "value": 0,
            "unit": "tokens/s",
            "vs_baseline": 1.0,
            "detail": {"ckpt_stage_error": str(e)[:300]},
        }
    throughput_tag = model_tag
    try:
        extra = bench_throughput(preset)
    except Exception as e:  # noqa: BLE001 - retry one size down
        print(
            f"bench: throughput at {model_tag} failed ({e}); "
            "retrying tiny", file=sys.stderr, flush=True,
        )
        try:
            extra = bench_throughput("tiny")
            extra["throughput_fallback"] = f"{model_tag} failed: {str(e)[:200]}"
            throughput_tag = "llama-tiny"
        except Exception as e2:  # noqa: BLE001
            extra = {"throughput_error": str(e2)[:300]}
    result.setdefault("detail", {}).update(extra)
    if "ckpt_stage_error" in result["detail"] and extra.get("tokens_per_sec"):
        # only an explicitly FAILED ckpt stage surrenders the headline
        # (a successful 0.000s blocking save must keep it), and the
        # label must name the model that actually produced the number
        result["metric"] = (
            f"train_tokens_per_sec ({throughput_tag}, single chip)"
        )
        result["value"] = extra["tokens_per_sec"]
        result["unit"] = "tokens/s"
    if os.getenv("DLROVER_TPU_BENCH_SKIP_DIST_CKPT", "") != "1":
        # distributed-commit persist scaling + differential/partial-read
        # accounting — disk-side, backend-independent (the satellite
        # metrics the ROADMAP's Orbax-grade checkpointing item names)
        result.setdefault("detail", {})["dist_ckpt"] = (
            _dist_ckpt_evidence()
        )
    if os.getenv("DLROVER_TPU_BENCH_SKIP_GRAD_SYNC", "") != "1":
        # grad-sync policy comparison (r6 post-backward per-leaf sync vs
        # r14 overlapped bucketed sync, exact/int8/int4/blockwise, with
        # overlap-efficiency + per-bucket bytes): CPU-mesh drill, cheap
        # and backend-independent.
        # the subprocess itself writes BENCH_grad_overlap.json AND
        # BENCH_comm.json (repo root) before printing its result line —
        # no second write here
        grad_sync = _grad_sync_evidence()
        result.setdefault("detail", {})["grad_sync"] = grad_sync
        if isinstance(grad_sync, dict) and grad_sync.get("comm"):
            # surface the comm observatory (per-bucket attribution +
            # probe-measured axis fabric) as its own detail section
            result["detail"]["comm"] = grad_sync["comm"]
    if fa_entry is not None:
        result.setdefault("detail", {})["fa_autotune"] = fa_entry
    if on_device_recovery is not None:
        result.setdefault("detail", {}).update({
            f"on_device_{k}": v for k, v in on_device_recovery.items()
        })
    if (
        os.getenv("DLROVER_TPU_BENCH_SKIP_GOODPUT", "") != "1"
        and os.getenv("DLROVER_TPU_BENCH_PRESET", "default") != "tiny"
    ):
        # goodput under injected faults — the reference's headline metric
        # (README.md:61-67: goodput 69% -> 95% with fault tolerance).
        # Always CPU-side (it drives a local master + agent + worker
        # stack); the TPU chip is not involved.
        try:
            from dlrover_tpu.diagnosis.goodput_drill import run_goodput_drill

            drill = run_goodput_drill()
            result.setdefault("detail", {})["goodput"] = drill
        except Exception as e:  # noqa: BLE001 - bench must print its line
            result.setdefault("detail", {})["goodput"] = {
                "drill_error": str(e)[:400]
            }
    if os.getenv("DLROVER_TPU_BENCH_SKIP_PEER_RECOVERY", "") != "1":
        # checkpoint-free fast recovery (r24): the peer-replicated
        # restore measured against the manifest-read rung it replaces —
        # recovery_mttr_s / peer_read_gbps are gate-watched history
        # columns.  Loopback-HTTP + shm in-process: CPU-side, seconds.
        # The round also lands in
        # BENCH_recovery.json so the recovery trajectory has its own
        # artifact.
        try:
            from dlrover_tpu.trainer.flash_checkpoint import (
                bench as ckpt_bench_mod,
            )

            recovery = ckpt_bench_mod.peer_recovery_bench()
            result.setdefault("detail", {})["peer_recovery"] = recovery
            with open("BENCH_recovery.json", "w") as f:
                json.dump(recovery, f, indent=2, default=str)
        except Exception as e:  # noqa: BLE001 - bench must print its line
            result.setdefault("detail", {})["peer_recovery"] = {
                "error": str(e)[:400]
            }
    if (
        os.getenv("DLROVER_TPU_BENCH_SKIP_FLEET", "") != "1"
        and os.getenv("DLROVER_TPU_BENCH_PRESET", "default") != "tiny"
    ):
        # control-plane fleet bench: 1k simulated agents through the
        # real servicer in poll AND longpoll modes (the ≥10x RPC
        # reduction headline) + a 10k-session storm proving admission
        # control bounds p99.  CPU-side by construction.  The full
        # report (with RED
        # snapshots before/after each mode) is ALSO written to
        # BENCH_fleet.json so the round file exists even if this
        # process dies before printing.
        fleet = {}
        try:
            from dlrover_tpu.diagnosis import fleet_bench

            fleet_cfg = fleet_bench.FleetConfig(
                agents=int(
                    os.getenv("DLROVER_TPU_BENCH_FLEET_AGENTS", "1000")
                ),
                agent_deadline_s=600.0,
                **fleet_bench.HEADLINE_SHAPE,
            )
            fleet = fleet_bench.run_fleet(fleet_cfg)
            # write the 1k comparison immediately: the 10k storm is the
            # leg most likely to die, and it must not take the finished
            # poll-vs-longpoll numbers down with it
            with open("BENCH_fleet.json", "w") as f:
                json.dump(fleet, f, indent=2, default=str)
            storm_cfg = fleet_bench.FleetConfig(
                agents=int(
                    os.getenv("DLROVER_TPU_BENCH_STORM_AGENTS", "10000")
                ),
                workload="storm", fanout=384, mode="longpoll",
                agent_deadline_s=600.0,
            )
            fleet["storm_10k"] = fleet_bench.run_mode(storm_cfg)
            result.setdefault("detail", {})["fleet_bench"] = fleet
            with open("BENCH_fleet.json", "w") as f:
                json.dump(fleet, f, indent=2, default=str)
        except Exception as e:  # noqa: BLE001 - bench must print its line
            # keep whatever completed (a storm crash must not lose the
            # finished 1k comparison from the round detail)
            result.setdefault("detail", {})["fleet_bench"] = {
                **fleet, "error": str(e)[:400]
            }
        # Brain v2 multi-job fleet bench: Brain-on vs static allocation
        # over the churning 4-job scenario — the fleet_goodput_gain
        # headline is a gate-watched BENCH_history column.  Pure CPU
        # simulation over the real stores/incident engine; seconds.
        try:
            from dlrover_tpu.diagnosis import brain_bench

            brain = brain_bench.run_bench()
            brain["assertions"] = {
                "problems": brain_bench.assert_bench(brain)
            }
            result.setdefault("detail", {})["brain_bench"] = brain
            with open("BENCH_brain.json", "w") as f:
                json.dump(brain, f, indent=2, default=str)
        except Exception as e:  # noqa: BLE001 - bench must print its line
            result.setdefault("detail", {})["brain_bench"] = {
                "error": str(e)[:400]
            }
    # flight-recorder overhead: the recorder is ALWAYS ON, so its
    # append cost is a per-step tax on every training run.  Record it
    # per round as a fraction of the measured step (acceptance: < 1%)
    # so a regression on the append path shows in the BENCH trajectory.
    try:
        from dlrover_tpu.observability import flight_recorder

        append_s = flight_recorder.measure_overhead()
        # appends per step on the instrumented paths: 1 step timing +
        # ~2 training events + ~5 finished spans of a checkpointing
        # step — a deliberately pessimistic budget
        appends_per_step = 8
        step_ms = result.get("detail", {}).get("step_ms")
        entry = {
            "append_us": round(append_s * 1e6, 3),
            "appends_per_step_budget": appends_per_step,
        }
        if step_ms:
            entry["pct_of_step"] = round(
                100.0 * append_s * appends_per_step / (step_ms / 1e3), 4
            )
        result.setdefault("detail", {})["flight_recorder"] = entry
    except Exception as e:  # noqa: BLE001 - bench must print its line
        result.setdefault("detail", {})["flight_recorder"] = {
            "error": str(e)[:200]
        }
    # this process's goodput-ledger account: the bench run's own wall
    # clock attributed across phases (the flash saves/restores above
    # charged ckpt_stall; the throughput loop charged compute) — the
    # per-round ledger summary the history trajectory records
    try:
        from dlrover_tpu.observability import goodput

        result.setdefault("detail", {})["goodput_ledger"] = (
            goodput.ledger().summary()
        )
    except Exception as e:  # noqa: BLE001 - bench must print its line
        result.setdefault("detail", {})["goodput_ledger"] = {
            "error": str(e)[:200]
        }
    # this process's memory account: one fresh sample (device stats +
    # host RSS/shm + the subsystem attribution) so the per-round
    # history records where the bytes went alongside where the seconds
    # went — on TPU rounds these are real memory_stats() numbers
    try:
        from dlrover_tpu.observability import memscope

        account = memscope.scope().sample()
        result.setdefault("detail", {})["mem_account"] = {
            "used_b": account["used_b"],
            "limit_b": account["limit_b"],
            "peak_b": account["peak_b"],
            "headroom_b": account["headroom_b"],
            "host_rss_b": account["host"]["rss_b"],
            "shm_b": account["host"]["shm_b"],
            "subsystems": account["subsystems"],
            "account_ok": account["account_ok"],
        }
    except Exception as e:  # noqa: BLE001 - bench must print its line
        result.setdefault("detail", {})["mem_account"] = {
            "error": str(e)[:200]
        }
    # compile observatory: this process's compile account — the bench's
    # jitted programs ran through the watched trainer call sites, so
    # per-round compile seconds and the persistent-cache hit ratio land
    # in the history trajectory (and the per-round regression gate
    # watches both: compile_s up or cache_hit_ratio down is a
    # regression)
    try:
        from dlrover_tpu.observability import jitscope

        result.setdefault("detail", {})["compile_observatory"] = (
            jitscope.scope().summary()
        )
    except Exception as e:  # noqa: BLE001 - bench must print its line
        result.setdefault("detail", {})["compile_observatory"] = {
            "error": str(e)[:200]
        }
    # RED-metrics snapshot: the bench run exercised flash-checkpoint
    # and (in the drills) control-plane RPC paths — the per-round
    # counters/histograms make a perf regression attributable from the
    # BENCH JSON alone (retry storms, ckpt phase inflation, error rates)
    try:
        from dlrover_tpu.observability import metrics as obs_metrics

        result.setdefault("detail", {})["red_metrics"] = (
            obs_metrics.registry().snapshot()
        )
    except Exception as e:  # noqa: BLE001 - bench must print its line
        result.setdefault("detail", {})["red_metrics"] = {
            "error": str(e)[:200]
        }
    # append the round to the machine-readable trajectory and judge it
    # against the recorded history (the bench-side regression sentinel);
    # the JSON line ALWAYS prints — the hard gate only flips the exit
    gate_failed = _history_and_gate(result, preset)
    print(json.dumps(result))
    if gate_failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
