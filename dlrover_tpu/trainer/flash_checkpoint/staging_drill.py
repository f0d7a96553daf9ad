"""Staging-throughput drill: two-phase vs streaming snapshot data path.

Measures, fully on CPU (``JAX_PLATFORMS=cpu``, fake multi-MB arrays,
tmpfs-backed storage), the two quantities the streaming rewrite exists
to move:

- **host peak-RSS delta** during staging: the two-phase path
  materializes the entire state as host arrays and THEN memcpys them
  into shm (device copy + host copy + shm live at once); streaming lands
  each chunk directly at its final shm offset, so its peak is shm + one
  chunk.
- **staging wall time**: streaming drops the second full-payload memcpy
  and overlaps each chunk's D2H with the previous chunk's shm write.

Also reported: D2H throughput, staged-step inflation against a
concurrent fake train loop (same step-clock/pacer machinery the real
stager uses), host copies per chunk (the zero-copy invariant), a
bit-exact shm read-back check per path, and a persist leg timing the
parallel chunked CRC writer pool against a single writer.

Each staging path runs in its own subprocess so RSS peaks can't bleed
between them; ``main()`` composes one ``STAGING_DRILL {json}`` line for
``bench.py``.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import zlib
from typing import Dict, Optional

from dlrover_tpu.common import envs
REPO = os.path.dirname(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
)

_ROLE_MARK = "STAGE_ROLE "
_MARK = "STAGING_DRILL "


def _payload_mb() -> int:
    return max(16, envs.get_int("DLROVER_TPU_STAGING_DRILL_MB"))


def _chunk_bytes() -> int:
    """Pinned staging chunk for BOTH paths: on CPU the pacer's collapsed
    step baseline would otherwise run unpaced whole-shard transfers,
    hiding exactly the per-chunk copy behavior the drill compares."""
    mb = max(1, envs.get_int("DLROVER_TPU_STAGING_DRILL_CHUNK_MB"))
    return mb << 20


def _rss_bytes() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    return 0


class _RssSampler:
    """Peak-RSS watcher: /proc sampling beats ru_maxrss here because the
    two phases run in one process lifetime in the role subprocess (the
    jax runtime warms up first) and ru_maxrss never comes back down."""

    def __init__(self, period_s: float = 0.005):
        self._period = period_s
        self._peak = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def __enter__(self):
        self.baseline = _rss_bytes()
        self._peak = self.baseline

        def run():
            while not self._stop.is_set():
                self._peak = max(self._peak, _rss_bytes())
                time.sleep(self._period)

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(5)
        self._peak = max(self._peak, _rss_bytes())

    @property
    def peak_delta(self) -> int:
        return max(0, self._peak - self.baseline)


def _fake_state(total_mb: int):
    """Dict of multi-MB fp32 jax arrays (committed to the CPU device) —
    the shapes are tall so the row-block streaming chunker has real work."""
    import jax.numpy as jnp
    import numpy as np

    n_leaves = 12
    per_leaf = total_mb * (1 << 20) // n_leaves
    rows = per_leaf // (256 * 4)
    rng = np.random.default_rng(0)
    return {
        f"w{i}": jnp.asarray(
            rng.standard_normal((rows, 256)).astype(np.float32)
        )
        for i in range(n_leaves)
    }


def _fake_train_loop(stop: threading.Event, durations: list):
    """Concurrent jitted matmul loop feeding the global step clock —
    what the pacer throttles staging against."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.utils.step_clock import get_step_clock

    clock = get_step_clock()
    x = jnp.ones((1536, 1536), jnp.float32)
    f = jax.jit(lambda a: a @ a + 1.0)
    f(x).block_until_ready()  # compile outside the measurement
    while not stop.is_set():
        t0 = time.perf_counter()
        f(x).block_until_ready()
        dt = time.perf_counter() - t0
        clock.record(dt)
        durations.append(dt)


def run_role(role: str) -> Dict:
    """One staging path, measured in isolation.  ``role`` is
    ``two_phase`` or ``streaming``."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from dlrover_tpu.common.multi_process import SharedMemoryBuffer
    from dlrover_tpu.trainer.flash_checkpoint import snapshot
    from dlrover_tpu.utils.step_clock import get_step_clock

    total_mb = _payload_mb()
    state = _fake_state(total_mb)
    payload = sum(int(a.size) * 4 for a in state.values())
    expect = {k: np.asarray(v) for k, v in state.items()}

    # EVENTS and BYTES both: the two-phase path's second full memcpy
    # (write_snapshot) is one event per SHARD but a whole shard's bytes,
    # so the honest copies-per-chunk ratio is byte-weighted
    staged = snapshot.StageCounters()
    clock = get_step_clock()
    clock.reset()
    # calm baseline: a few steps before staging starts
    durations: list = []
    stop = threading.Event()
    loop = threading.Thread(
        target=_fake_train_loop, args=(stop, durations), daemon=True
    )
    loop.start()
    while len(durations) < 4:
        time.sleep(0.01)
    base_steps = sorted(durations[:4])
    base_step_s = base_steps[len(base_steps) // 2]

    shm = SharedMemoryBuffer(f"stagedrill_{role}_{os.getpid()}")
    overlap: list = []
    try:
        mark = len(durations)
        with _RssSampler() as rss:
            t0 = time.perf_counter()
            pacer = snapshot.StagePacer()
            # pin the chunk size: identical granularity for both paths
            # (manual_pace routes gate() around the adaptive control
            # law, and ~0 pace means no duty-cycle sleeps)
            pacer.chunk_bytes = _chunk_bytes()
            pacer._calibrated = True
            pacer.manual_pace = 1e-9
            pacer.clock.staging_started()
            try:
                if role == "two_phase":
                    t_d2h = time.perf_counter()
                    leaves = snapshot.extract_host_shards(
                        state, throttled=True, pacer=pacer, counters=staged
                    )
                    d2h_s = time.perf_counter() - t_d2h
                    snapshot.write_snapshot(shm, 1, leaves, counters=staged)
                else:
                    leaves = snapshot.plan_shards(state)
                    snapshot.stream_snapshot(
                        shm, 1, leaves, pacer=pacer,
                        chunk_bytes=_chunk_bytes(), release_shards=False,
                        counters=staged,
                    )
                    d2h_s = None  # fused with the shm write by design
            finally:
                pacer.clock.staging_finished()
            wall_s = time.perf_counter() - t0
        overlap = durations[mark:]
        stop.set()
        loop.join(10)

        # bit-exact read-back through the shm format
        meta = snapshot.read_snapshot_meta(shm)
        assert meta is not None and meta["step"] == 1
        roundtrip_ok = True
        for leaf in meta["leaves"]:
            m = snapshot.ShardIndexMap(leaf["dtype"], leaf["gshape"])
            for sm in leaf["shards"]:
                m.add(
                    sm["index"],
                    snapshot.read_shard_bytes(shm, meta, sm, leaf["dtype"]),
                )
            got = m.read(tuple(slice(0, d) for d in leaf["gshape"]))
            if not np.array_equal(got, expect[leaf["path"]]):
                roundtrip_ok = False
    finally:
        stop.set()
        shm.unlink()

    olap = sorted(overlap) if overlap else [base_step_s]
    overlap_med = olap[len(olap) // 2]
    result = {
        "payload_mb": round(payload / (1 << 20), 1),
        "staging_wall_s": round(wall_s, 3),
        "staging_gbps": round(payload / 1e9 / max(wall_s, 1e-9), 3),
        "host_peak_rss_delta_mb": round(rss.peak_delta / (1 << 20), 1),
        "chunks": staged.chunks,
        "host_copies": staged.host_copies,
        "host_copies_per_chunk": round(
            staged.host_copies / max(staged.chunks, 1), 2
        ),
        # byte-weighted: total host-side bytes copied per byte staged —
        # the metric the zero-copy claim is actually about (2.0 for the
        # two-phase intermediate+memcpy, 1.0 for streaming)
        "host_copy_bytes_x": round(
            staged.host_copy_bytes / max(staged.bytes, 1), 2
        ),
        "step_s_base": round(base_step_s, 4),
        "step_s_during_staging": round(overlap_med, 4),
        "staged_step_inflation_x": round(
            overlap_med / max(base_step_s, 1e-9), 2
        ),
        "roundtrip_ok": roundtrip_ok,
    }
    if d2h_s is not None:
        result["d2h_s"] = round(d2h_s, 3)
    return result


def _persist_leg() -> Dict:
    """Parallel chunked CRC writer pool vs a single writer, on tmpfs
    when available (/dev/shm) so the numbers measure the writer, not a
    spinning disk."""
    import numpy as np

    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
    from dlrover_tpu.common.storage import PosixDiskStorage, chunk_spans

    base = "/dev/shm" if os.access("/dev/shm", os.W_OK) else None
    out_dir = tempfile.mkdtemp(prefix="dlrover_tpu_persist_", dir=base)
    storage = PosixDiskStorage()
    payload = np.random.default_rng(0).integers(
        0, 255, size=_payload_mb() * (1 << 20), dtype=np.uint8
    )
    writers, chunk_bytes = AsyncCheckpointSaver._persist_pool_config()
    blob = None
    try:
        results = {}
        for tag, nwriters in (("single", 1), ("pool", writers)):
            path = os.path.join(out_dir, f"{tag}.bin")
            t0 = time.perf_counter()
            records = storage.write_chunks(
                memoryview(payload), path, chunk_bytes=chunk_bytes,
                writers=nwriters,
            )
            dt = time.perf_counter() - t0
            results[f"{tag}_writer_s"] = round(dt, 3)
            results[f"{tag}_writer_gbps"] = round(
                payload.nbytes / 1e9 / max(dt, 1e-9), 3
            )
        # integrity: recorded CRCs match the bytes on disk...
        blob = storage.read_binary(os.path.join(out_dir, "pool.bin"))
        crc_ok = all(
            zlib.crc32(memoryview(blob[r["offset"]:r["offset"] + r["nbytes"]]))
            == r["crc32"]
            for r in records
        )
        # ...and a flipped byte is caught
        blob = None
        with open(os.path.join(out_dir, "pool.bin"), "r+b") as f:
            f.seek(records[0]["offset"])
            byte = f.read(1)
            f.seek(records[0]["offset"])
            f.write(bytes([byte[0] ^ 0xFF]))
        blob = storage.read_binary(os.path.join(out_dir, "pool.bin"))
        first = records[0]
        corrupted_detected = (
            zlib.crc32(
                memoryview(blob[first["offset"]:first["offset"] + first["nbytes"]])
            )
            != first["crc32"]
        )
        results.update({
            "writers": writers,
            "chunk_mb": chunk_bytes // (1 << 20),
            "n_chunks": len(chunk_spans(payload.nbytes, chunk_bytes)),
            "crc_ok": bool(crc_ok),
            "crc_detects_corruption": bool(corrupted_detected),
            "tmpfs": base is not None,
        })
        return results
    finally:
        del blob
        shutil.rmtree(out_dir, ignore_errors=True)


def main() -> int:
    if len(sys.argv) > 1:
        # role subprocess: one staging path, isolated RSS
        print(_ROLE_MARK + json.dumps(run_role(sys.argv[1])), flush=True)
        return 0
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    out: Dict = {}
    for role in ("two_phase", "streaming"):
        try:
            proc = subprocess.run(
                [sys.executable, "-m",
                 "dlrover_tpu.trainer.flash_checkpoint.staging_drill",
                 role],
                capture_output=True, text=True, timeout=600, env=env,
                cwd=REPO,
            )
            for line in proc.stdout.splitlines():
                if line.startswith(_ROLE_MARK):
                    out[role] = json.loads(line[len(_ROLE_MARK):])
                    break
            else:
                out[role] = {
                    "error": f"rc={proc.returncode}: "
                    + (proc.stderr or proc.stdout)[-300:]
                }
        except (subprocess.TimeoutExpired, OSError) as e:
            out[role] = {"error": str(e)[:300]}
    two, stream = out.get("two_phase", {}), out.get("streaming", {})
    if "error" not in two and "error" not in stream:
        out["streaming_vs_two_phase"] = {
            "wall_x": round(
                two["staging_wall_s"] / max(stream["staging_wall_s"], 1e-9),
                2,
            ),
            "rss_x": round(
                two["host_peak_rss_delta_mb"]
                / max(stream["host_peak_rss_delta_mb"], 1e-9),
                2,
            ),
        }
    try:
        out["persist"] = _persist_leg()
    except Exception as e:  # noqa: BLE001 - the staging legs stand alone
        out["persist"] = {"error": str(e)[:300]}
    print(_MARK + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
