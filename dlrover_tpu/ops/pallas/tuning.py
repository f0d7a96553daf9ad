"""Flash-attention block-size tuning: on-device sweep + persisted table.

The Pallas kernel's ``block_q``/``block_kv`` determine VMEM footprint and
MXU utilisation; the right values depend on sequence length, head dim and
TPU generation, and guessing them costs real throughput.  This module

- resolves tuned block sizes from a JSON table: the shipped
  ``fa_tuned.json``, overlaid by the file ``DLROVER_TPU_FA_TUNING`` names
  when it is set — nothing outside the checkout is read otherwise, and
- provides the ``autotune`` sweep that MEASURES candidates on the current
  accelerator and writes the winners to that file (or ``-o``), run as::

      python -m dlrover_tpu.ops.pallas.tuning --seq 2048 --head-dim 128 \
          -o my_table.json

Sweeping requires a real TPU backend — on CPU the kernel only interprets,
whose timings say nothing about Mosaic codegen, so the CLI refuses.

An entry says how it was measured.  ``"sync": "hard_block"`` is this
module's sweep: the kernel alone, forward and backward, timed from the host
around a read-back (``s2048_d128``).  ``"measured"`` is
``scripts/fa_blocks_in_step.py``: the kernels' own time in the device trace
of a whole training step, forward, recomputed forward and backward of
every layer, with ``kernel_ms_per_step``, ``device_kind`` and ``date``
(``s1024_d64``, GPT-2's shape, swept anew on the ``[B, S, H*D]`` interface
where a grid step is two heads: TPU v5 lite, 2026-09-27, 1024x1024 at
116.6 ms a step of 96 calls; 512x1024 129.8, 512x512 152.7, 128x128
472.3: at this shape one block a head-pair, with nothing skipped, still
beats every split that skips the masked blocks; the forward alone goes
54.3 -> 108.4 -> 158.6 -> 210.2 ms as 1024 rows meet 1, 2, 4, 8 kv
blocks: its cost is by kv block visited, not by score computed).
"""

import argparse
import functools
import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.common.log import logger
from dlrover_tpu.common import envs

DEFAULT_BLOCKS = (512, 512)
_SHIPPED = os.path.join(os.path.dirname(__file__), "fa_tuned.json")


def _write_path() -> str:
    """Where autotune persists: only where ``DLROVER_TPU_FA_TUNING``
    points — NEVER the installed package dir (read-only installs; source
    dirt) and never a per-user file a later run would read in silence."""
    path = envs.get_str("DLROVER_TPU_FA_TUNING")
    if not path:
        raise RuntimeError(
            "autotune has nowhere to write: set DLROVER_TPU_FA_TUNING "
            "or pass an output path"
        )
    return path


@functools.lru_cache(maxsize=4)
def _load_one(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _load_table() -> Dict:
    """Effective table: shipped defaults overlaid by an explicit env
    table."""
    table = dict(_load_one(_SHIPPED))
    env = envs.get_str("DLROVER_TPU_FA_TUNING")
    if env:
        table.update(_load_one(env))
    return table


def _shape_suffix(head_dim: int, window: Optional[int] = None) -> str:
    """What a key ends in: the head size and, of a windowed call, the
    window.  Such entries stand apart (``s16384_d128_w512``): the grid is
    another shape, and no causal call's blocks move for them."""
    return f"_d{head_dim}" + ("" if window is None else f"_w{window}")


def _key(seq_len: int, head_dim: int, window: Optional[int] = None) -> str:
    return f"s{seq_len}" + _shape_suffix(head_dim, window)


def _shrink_to_divisor(seq_len: int, block: int) -> int:
    while block > 1 and seq_len % block:
        block //= 2
    return max(1, block)


def _entry_blocks(entry) -> Optional[Tuple[int, int]]:
    """Validated (block_q, block_kv) from a table entry, None if bad."""
    try:
        block_q = int(entry["block_q"])
        block_kv = int(entry["block_kv"])
    except (TypeError, KeyError, ValueError):
        return None
    if block_q <= 0 or block_kv <= 0:
        return None
    return block_q, block_kv


def tuned_blocks(seq_len: int, head_dim: int,
                 window: Optional[int] = None) -> Tuple[int, int]:
    """Best-known (block_q, block_kv) for this shape: exact table hit,
    else the entry with the nearest sequence length at the same head
    dim (and the same window, or none), else the untuned default.  A
    malformed table (hand-edited)
    must degrade to the default, never crash the forward pass —
    same fail-safe contract as ``_load_one``."""
    fallback = (
        _shrink_to_divisor(seq_len, min(DEFAULT_BLOCKS[0], seq_len)),
        _shrink_to_divisor(seq_len, min(DEFAULT_BLOCKS[1], seq_len)),
    )
    try:
        table = _load_table()
        blocks = _entry_blocks(
            table.get(_key(seq_len, head_dim, window)) or {})
        if blocks:
            return (
                _shrink_to_divisor(seq_len, blocks[0]),
                _shrink_to_divisor(seq_len, blocks[1]),
            )
        same_dim = []
        for k, v in table.items():
            if not k.endswith(_shape_suffix(head_dim, window)):
                continue
            try:
                dist = abs(int(k.split("_")[0][1:]) - seq_len)
            except ValueError:
                continue  # hostile/malformed key
            blocks = _entry_blocks(v)
            if blocks:
                same_dim.append((dist, blocks))
        if same_dim:
            _, (block_q, block_kv) = min(same_dim, key=lambda kv: kv[0])
            # a borrowed entry may not divide this sequence; shrink to
            # fit (never clamp up — a non-divisor makes the kernel raise)
            return (
                _shrink_to_divisor(seq_len, block_q),
                _shrink_to_divisor(seq_len, block_kv),
            )
    except Exception as e:  # noqa: BLE001 - tuning must never break fwd
        logger.warning("tuning table unusable (%s); using defaults", e)
    return fallback


def selected_tiling(block_q: int, head_dim: int) -> Tuple[int, int]:
    """``(block_kv, mean_block_kv)`` of the selected attention's kernels
    (``selected_attention.py``) for blocks of ``block_q`` queries: the keys
    a tile at most in the forward and backward kernels and in the kernel
    of the heads' mean (a call takes the largest tile under it that
    divides its keys).  The table's ``selected_q<block_q>_d<head_dim>_kv``
    entry (swept in a whole step on the chip by
    ``scripts/fa_blocks_in_step.py --selected``; one swept where no
    heads' mean runs names no tile for it, and that reads as the other's),
    else the untuned default."""
    try:
        entry = _load_table().get(f"selected_q{block_q}_d{head_dim}_kv") or {}
        block_kv = int(entry["block_kv"])
        tiling = block_kv, int(entry.get("mean_block_kv", block_kv))
        if min(tiling) > 0:
            return tiling
    except (TypeError, KeyError, ValueError):
        pass
    return DEFAULT_BLOCKS[1], DEFAULT_BLOCKS[1]


def index_tiling(block_q: int, index_dim: int) -> Tuple[int, int]:
    """``(block_kv, unroll)`` of the index scores' kernels
    (``index_scores.py``) for blocks of ``block_q`` queries at index heads
    of ``index_dim``: the keys a tile at most, forward and backward, and
    the 128-lane column blocks of ``q_I`` that are straight-line code a
    turn of the loop over them.  The table's
    ``index_q<block_q>_c<index_dim>_kv`` entry (swept in a whole step on
    the chip by ``scripts/fa_blocks_in_step.py --index``), else the untuned
    default."""
    try:
        entry = _load_table().get(f"index_q{block_q}_c{index_dim}_kv") or {}
        tiling = int(entry["block_kv"]), int(entry.get("unroll", 1))
        if min(tiling) > 0:
            return tiling
    except (TypeError, KeyError, ValueError):
        pass
    return DEFAULT_BLOCKS[1], 1


def kda_tiling(chunk: int, head_dim: int) -> Tuple[int, int, int]:
    """``(chunks, heads, state_heads)`` of the delta rule's kernels
    (``kda.py``) for chunks of ``chunk`` positions at heads of
    ``head_dim``: the chunks a grid step of every kernel, the heads a turn
    of the chunk kernels' loop over the heads, and the heads a grid step
    of the kernels that carry the state.  The table's
    ``kda_c<chunk>_d<head_dim>`` entry (swept in the whole step on the
    chip by ``scripts/fa_blocks_in_step.py --kda``), else one of each."""
    try:
        entry = _load_table().get(f"kda_c{chunk}_d{head_dim}") or {}
        tiling = (int(entry["chunks"]), int(entry["heads"]),
                  int(entry.get("state_heads", entry["heads"])))
        if min(tiling) > 0:
            return tiling
    except (TypeError, KeyError, ValueError):
        pass
    return 1, 1, 1


def _current_device_kind() -> str:
    try:
        import jax

        return jax.devices()[0].device_kind
    except Exception:  # noqa: BLE001 - no backend: unknown kind
        return ""


def trusted_entry(
    seq_len: int, head_dim: int, shape: Optional[List[int]] = None
) -> Optional[Dict]:
    """A table entry safe to REUSE as a measured winner: trustworthy
    timing provenance (``sync == "hard_block"``), measured at the exact
    requested shape, and — when the entry records one — on the same chip
    model as the current backend.  ``None`` means re-tune."""
    try:
        entry = _load_table().get(_key(seq_len, head_dim))
    except Exception:  # noqa: BLE001 - unreadable table: re-tune
        return None
    if not entry or entry.get("sync") != "hard_block":
        return None
    if shape is not None and entry.get("shape") != list(shape):
        return None
    # entries that never recorded a chip model predate the device_kind
    # field; they may have been tuned on a different TPU generation, so
    # they are NOT trusted for reuse (one re-tune refreshes them)
    if entry.get("device_kind") != _current_device_kind():
        return None
    return dict(entry)


def _candidates(seq_len: int) -> List[Tuple[int, int]]:
    sizes = [s for s in (128, 256, 512, 1024) if seq_len % s == 0]
    return [(bq, bkv) for bq in sizes for bkv in sizes]


def _time_fn(fn, *args, iters: int = 10) -> float:
    from dlrover_tpu.utils.timing import hard_block

    hard_block(fn(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    # hard_block (a device->host read of the result) rather than
    # block_until_ready: a backend that resolved ready events at enqueue
    # would rank candidates by dispatch noise
    hard_block(out)
    return (time.perf_counter() - t0) / iters


def autotune(
    seq_len: int,
    head_dim: int = 128,
    heads: int = 8,
    batch: int = 1,
    causal: bool = True,
    out_path: Optional[str] = None,
    require_tpu: bool = True,
) -> Dict:
    """Sweep (block_q, block_kv) over the fwd+bwd kernel on the CURRENT
    backend; persist and return the winner entry."""
    import jax
    import jax.numpy as jnp

    if require_tpu and jax.default_backend() != "tpu":
        raise RuntimeError(
            "autotune must run on a TPU backend (CPU interprets the "
            "kernel; its timings say nothing about Mosaic codegen)"
        )
    path = out_path or _write_path()  # before the sweep: fail early
    from dlrover_tpu.ops.pallas.flash_attention import (
        pallas_flash_attention,
    )

    key = jax.random.PRNGKey(0)
    shape = (batch, seq_len, heads, head_dim)
    q = jax.random.normal(key, shape, jnp.bfloat16)
    k = jax.random.normal(key, shape, jnp.bfloat16)
    v = jax.random.normal(key, shape, jnp.bfloat16)

    results = []
    for block_q, block_kv in _candidates(seq_len):

        def step(q, k, v, _bq=block_q, _bkv=block_kv):
            def loss(q):
                return pallas_flash_attention(
                    q, k, v, causal=causal, block_q=_bq, block_kv=_bkv
                ).astype(jnp.float32).sum()

            value, grad = jax.value_and_grad(loss)(q)
            return grad, value

        try:
            elapsed = _time_fn(jax.jit(step), q, k, v)
        except Exception as e:  # noqa: BLE001 - VMEM overflow etc.
            logger.info("blocks (%d,%d) failed: %s", block_q, block_kv, e)
            continue
        results.append((elapsed, block_q, block_kv))
        logger.info(
            "blocks (%d,%d): %.3f ms", block_q, block_kv, elapsed * 1e3
        )
    if not results:
        raise RuntimeError("no candidate block size compiled")
    elapsed, block_q, block_kv = min(results)
    entry = {
        "block_q": block_q,
        "block_kv": block_kv,
        "ms": round(elapsed * 1e3, 4),
        "backend": jax.default_backend(),
        # chip model, not just backend: block rankings shift across TPU
        # generations, so a winner tuned on v5e must not be silently
        # trusted on v4/v6
        "device_kind": _current_device_kind(),
        "shape": list(shape),
        "causal": causal,
        # timing provenance: entries measured before the hard_block fix
        # were ranked by dispatch jitter and lack this field — treat
        # them as untrusted
        "sync": "hard_block",
    }
    table = dict(_load_one(path))
    table[_key(seq_len, head_dim)] = entry
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    _load_one.cache_clear()
    logger.info(
        "tuned s=%d d=%d -> blocks (%d,%d) %.3f ms (table: %s)",
        seq_len, head_dim, block_q, block_kv, elapsed * 1e3, path,
    )
    return entry


def main(argv=None) -> int:
    parser = argparse.ArgumentParser("flash-attention autotune")
    parser.add_argument("--seq", type=int, required=True)
    parser.add_argument("--head-dim", type=int, default=128)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--batch", type=int, default=1)
    parser.add_argument("--no-causal", action="store_true")
    parser.add_argument("-o", "--output", default="")
    args = parser.parse_args(argv)
    entry = autotune(
        args.seq, args.head_dim, args.heads, args.batch,
        causal=not args.no_causal, out_path=args.output or None,
    )
    print(json.dumps(entry))
    return 0


if __name__ == "__main__":
    sys.exit(main())
