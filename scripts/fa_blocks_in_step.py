"""FA2 block sizes for one shape, ranked by the kernels' time inside a step.

``ops/pallas/tuning.py::autotune`` times the kernel alone from the host.
This sweep runs the whole training step of a benchmark configuration (its
``Trainer``, optimizer and batch: forward, recomputed forward and backward
of every layer) once for each candidate, under a profiler session, and
sums the device time of the attention module's ``tpu_custom_call``s in
the trace.  That is the time the step pays, and it is what the shipped
``s1024_d64`` entry of ``fa_tuned.json`` was chosen by.  On the chip::

    python3 scripts/fa_blocks_in_step.py --config gpt2m

``--selected`` sweeps instead the selected attention's kernels
(``ops/pallas/selected_attention.py``): keys a tile in the forward and
backward and in the heads' mean (``--blocks kv,mean_kv;kv,mean_kv``; the table's
``selected_q<block>_d<head_dim>_kv`` entry) in a configuration with an
indexer, forward, heads' mean and backward told apart by their results;
or, in a configuration whose attention goes by windows and summaries, keys
a tile alone (``--blocks kv;kv``: no indexer, no heads' mean)::

    python3 scripts/fa_blocks_in_step.py --config keyevl2_30b_1of8 --selected
    python3 scripts/fa_blocks_in_step.py --config evabyte_l4 --selected

``--index`` sweeps the kernels of the indexer's scores and their gradient
(``ops/pallas/index_scores.py``; ``--blocks kv,unroll;kv,unroll``: keys a
tile, and the 128-lane column blocks of ``q_I``, two heads of 64 each, that
are straight-line code a turn of the loop; the table's
``index_q<block>_c<index_dim>_kv`` entry), forward and backward told apart
by their results::

    python3 scripts/fa_blocks_in_step.py --config keyevl2_30b_1of8 --index

``--kda`` sweeps the gated delta rule's kernels (``ops/pallas/kda.py``;
``--blocks chunks,heads;chunks,heads``: the chunks a grid step and the
heads a turn of the chunk kernels' loop over the heads, the heads a grid
step of the kernels that walk the chunks as ``--state-heads``; the table's
``kda_c<chunk>_d<head_dim>`` entry), the work inside chunks and the walk
between them, forward and backward, told apart by their names and
results::

    python3 scripts/fa_blocks_in_step.py --config solaropen2_250b_1of32 --kda

Candidates reach the kernel through the table ``DLROVER_TPU_FA_TUNING``
names, as a user's own table would.  One JSON line a candidate, the
winner's table entry last.
"""

import argparse
import datetime
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tally(trace_dir, kinds, kind_of):
    """kind -> [events, seconds] over the operations the first chip ran;
    ``kind_of(text)`` names an operation's kind, or nothing."""
    from benchmarks import trace as trace_mod

    loaded = trace_mod.load(trace_mod.find_xplane(trace_dir))
    found = {kind: [0, 0.0] for kind in kinds}
    if loaded.device_ops:
        for name, start, end in loaded.device_ops[min(loaded.device_ops)]:
            kind = kind_of(name)
            if kind:
                found[kind][0] += 1
                found[kind][1] += end - start
    return found


def kernel_seconds(trace_dir):
    """kind (``fwd``, ``dq``, ``dkv``) -> [events, seconds] of the
    attention custom calls on the first chip, told apart as the
    benchmark's ``fa2_ms_per_step`` tells them."""
    from benchmarks import common

    return _tally(
        trace_dir, ("fwd", "dq", "dkv"),
        common.load_module("layer_metrics", "fa2_ms_per_step").kind_of)


SELECTED_CALL = re.compile(r"^%[\w.]+ = (.*) custom-call\(.*"
                           r'custom_call_target="tpu_custom_call"')


def selected_kernel_seconds(trace_dir, shape):
    """kind (``fwd``, ``mean``, ``bwd``) -> [events, seconds] of the
    custom calls that carry a block's mask, known as the benchmark's
    ``sparse_attn_ms_per_step`` (or, for windows and summaries,
    ``eva_attn_ms_per_step``) knows them: the forward returns two arrays,
    the heads' mean one, the backward three or, with the gradients of the
    keys every query attends to, five."""
    from benchmarks import common

    if "window" in shape:
        carries_mask = common.load_module(
            "layer_metrics", "eva_attn_ms_per_step").is_window_op
    else:
        carries_mask = common.load_module(
            "layer_metrics", "sparse_attn_ms_per_step").block_by_keys

    def kind_of(name):
        call = SELECTED_CALL.match(name)
        if not call or not carries_mask(name, shape):
            return None
        result = call.group(1)
        return ("mean" if not result.startswith("(") else
                "fwd" if result.count("[") == 2 else "bwd")

    return _tally(trace_dir, ("fwd", "mean", "bwd"), kind_of)


def index_kernel_seconds(trace_dir, shape):
    """kind (``fwd``, ``bwd``) -> [events, seconds] of the index scores'
    custom calls: they alone carry the index heads' weights ``f32[batch,
    block, index heads]``, an operand of both and, as ``dw``, a result of
    the backward, which returns three arrays where the forward returns
    ``I f32[batch, block, keys]`` alone."""
    weights = "f32[%d,%d,%d]" % (
        shape["batch"], shape["block"], shape["index_heads"])

    def kind_of(name):
        call = SELECTED_CALL.match(name)
        if not call or weights not in name:
            return None
        return "bwd" if call.group(1).startswith("(") else "fwd"

    return _tally(trace_dir, ("fwd", "bwd"), kind_of)


def kda_kernel_seconds(trace_dir):
    """kind (``chunk_fwd``, ``state_fwd``, ``state_bwd``, ``chunk_bwd``) ->
    [events, seconds] of the delta rule's custom calls: named after the
    scope around their call (``%chunk.N``, ``%state.N``), the forward of
    either told from its backward by how many arrays it returns."""
    kinds = {("chunk", 7): "chunk_fwd", ("state", 3): "state_fwd",
             ("state", 6): "state_bwd", ("chunk", 5): "chunk_bwd"}

    def kind_of(name):
        call = SELECTED_CALL.match(name)
        if not call:
            return None
        return kinds.get((name[1:].split(".")[0], call.group(1).count("[")))

    return _tally(trace_dir, tuple(kinds.values()), kind_of)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--config", default="gpt2m")
    parser.add_argument("--steps", type=int, default=3)
    parser.add_argument("--blocks", default="",
                        help="candidates as q,kv;q,kv (default: the sweep's)")
    parser.add_argument("--selected", action="store_true",
                        help="sweep the selected attention's kernels; "
                             "--blocks then takes kv,mean_kv;kv,mean_kv")
    parser.add_argument("--index", action="store_true",
                        help="sweep the index scores' kernels; --blocks "
                             "then takes kv,unroll;kv,unroll")
    parser.add_argument("--kda", action="store_true",
                        help="sweep the delta rule's kernels; --blocks "
                             "then takes chunks,heads;chunks,heads")
    parser.add_argument("--state-heads", type=int, default=4)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes, any backend: control flow only")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from benchmarks import program
    from dlrover_tpu.ops.pallas import tuning

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    family, model, trainer = program.make_trainer(config, args.rehearse)
    pool = program.make_pool(config, args.rehearse, 0, family)
    m = family.sizes(config, args.rehearse)
    batch, seq = pool[0]["input_ids"].shape
    heads = m.get("n_head") or m["num_attention_heads"]
    head_dim = m.get("head_dim") or m["n_embd"] // heads
    key = f"s{seq}_d{head_dim}"
    if args.selected:
        if hasattr(family, "sparse_attn_shape"):
            shape = family.sparse_attn_shape(config, batch, seq, args.rehearse)
            block_q = shape["block"]
            args.blocks = args.blocks or "512,512;1024,512;2048,512;2048,2048"
        else:   # windows and summaries: a window of queries a call
            shape = family.eva_attn_shape(config, batch, seq, args.rehearse)
            block_q = shape["window"]
            args.blocks = args.blocks or "512;1024;2048"
        key = f"selected_q{block_q}_d{head_dim}_kv"
    if args.index:
        shape = family.sparse_attn_shape(config, batch, seq, args.rehearse)
        block_q = shape["block"]
        args.blocks = args.blocks or "1024,1;2048,1;4096,1;2048,2;2048,4"
        heads, head_dim = shape["index_heads"], shape["index_dim"]
        key = f"index_q{block_q}_c{head_dim}_kv"
    if args.kda:
        shape = family.kda_shape(config, batch, seq, args.rehearse)
        args.blocks = args.blocks or "1,8;2,8;4,8;2,4;1,2"
        heads, head_dim = shape["heads"], shape["head_dim"]
        key = f"kda_c{model.config.kda_chunk}_d{head_dim}"
    if args.blocks:
        candidates = [tuple(int(b) for b in pair.split(","))
                      for pair in args.blocks.split(";")]
    else:
        candidates = tuning._candidates(seq)
    state = trainer.create_state(program.make_key(0), pool[0]["input_ids"])
    results = []
    with tempfile.TemporaryDirectory() as scratch:
        table = os.path.join(scratch, "candidate.json")
        os.environ["DLROVER_TPU_FA_TUNING"] = table
        for first, *second in candidates:
            second = second[0] if second else first
            if args.kda:
                line = {"chunks": first, "heads": second,
                        "state_heads": args.state_heads}
            elif args.index:
                line = {"block_q": block_q, "block_kv": first,
                        "unroll": second}
            elif args.selected:
                line = {"block_q": block_q, "block_kv": first}
                if "block" in shape:   # an indexer: a heads' mean
                    line["mean_block_kv"] = second
            else:
                line = {"block_q": first, "block_kv": second}
            with open(table, "w") as f:
                json.dump({key: line}, f)
            tuning._load_one.cache_clear()
            # a new Trainer traces the step anew, with this candidate
            _, _, trainer = program.make_trainer(config, args.rehearse)
            trainer.state_shardings = trainer.state_sharding_for(
                program.make_key(0), pool[0]["input_ids"])
            try:
                sharded = [trainer.shard_batch(b) for b in pool[:args.steps]]
                t0 = time.perf_counter()
                state, metrics = trainer.train_step(state, sharded[0])
                float(metrics["loss"])  # compiled, and one step through
                line["compile_and_first_step_s"] = round(
                    time.perf_counter() - t0, 1)
                trace_dir = os.path.join(scratch, f"t{first}_{second}")
                jax.profiler.start_trace(trace_dir)
                try:
                    t0 = time.perf_counter()
                    for on_mesh in sharded:
                        state, metrics = trainer.train_step(state, on_mesh)
                    float(metrics["loss"])
                    step_s = (time.perf_counter() - t0) / len(sharded)
                finally:
                    jax.profiler.stop_trace()
                found = (kda_kernel_seconds(trace_dir) if args.kda else
                         index_kernel_seconds(trace_dir, shape)
                         if args.index else
                         selected_kernel_seconds(trace_dir, shape)
                         if args.selected else kernel_seconds(trace_dir))
                per_step = 1e3 / len(sharded)
                line.update(
                    kernel_ms_per_step=round(
                        per_step * sum(s for _, s in found.values()), 3),
                    **{f"{kind}_ms_per_step": round(per_step * s, 3)
                       for kind, (_, s) in found.items()},
                    kernel_calls_per_step=sum(
                        n for n, _ in found.values()) / len(sharded),
                    step_ms_under_trace=round(1e3 * step_s, 2))
            except Exception as e:  # noqa: BLE001 - a candidate that cannot
                line["error"] = f"{type(e).__name__}: {e}"[:300]  # compile
            print(json.dumps(line), flush=True)
            results.append(line)
    ranked = sorted((r for r in results if r.get("kernel_ms_per_step")),
                    key=lambda r: r["kernel_ms_per_step"])
    if not ranked:
        print(json.dumps({"error": "no attention custom call in any trace "
                          f"(backend {jax.default_backend()!r})"}))
        return 1
    best = ranked[0]
    named = ("block_q", "block_kv", "mean_block_kv", "unroll", "chunks",
             "heads", "state_heads")
    runner_up = ("; %d candidates, next best %s at %s" % (
        len(ranked), "x".join(str(ranked[1][n]) for n in named
                              if n in ranked[1]),
        ranked[1]["kernel_ms_per_step"])) if len(ranked) > 1 else ""
    print(json.dumps({key: {
        **{n: best[n] for n in named if n in best},
        "kernel_ms_per_step": best["kernel_ms_per_step"],
        "kernel_calls_per_step": int(best["kernel_calls_per_step"]),
        "measured": "kernel time in the device trace of a whole step "
                    f"({args.config}: forward, recomputed forward, backward) "
                    "by scripts/fa_blocks_in_step.py%s%s" % (
                        " --kda" if args.kda else
                        " --index" if args.index else
                        " --selected" if args.selected else "", runner_up),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "date": datetime.date.today().isoformat(),
        "causal": True,
        "shape": [batch, seq, heads, head_dim],
    }}, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
