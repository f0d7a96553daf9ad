"""The per-host health-check workload.

TPU-native counterpart of reference ``dlrover/trainer/torch/node_check/``
(``utils.py:80-246`` bm_allgather/matmul, ``nvidia_gpu.py:40``): each check
group forms a tiny jax.distributed world and times (a) a bf16 matmul loop on
the local chips (MXU health) and (b) a psum+all_gather loop over the group
(ICI/DCN link health).  The elapsed time is written to a file the agent
reads and reports to the master, which classifies fault vs straggler hosts.

Fault injection for drills: ``DLROVER_TPU_MOCK_ERR_RANK=<process_id>``
raises inside the check (reference ``MOCK_ERR_RANK`` utils.py:52-57).
"""

import json
import sys
import time

from dlrover_tpu.common import envs
from dlrover_tpu.common.constants import NodeEnv


def _mock_error(process_id: int):
    mock = envs.get_str(NodeEnv.MOCK_ERR_RANK)
    if mock and int(mock) == process_id:
        raise RuntimeError(f"mock error on process {process_id}")


def _mock_slow(node_id: int):
    """Straggler injection for drills (pairs with --exclude-straggler)."""
    mock = envs.get_str("DLROVER_TPU_MOCK_SLOW_NODE")
    if mock and int(mock) == node_id:
        time.sleep(envs.get_float("DLROVER_TPU_MOCK_SLOW_SECS"))


def run_check(out_path: str) -> float:
    from dlrover_tpu.trainer.bootstrap import init

    ctx = init()
    _mock_error(ctx.process_id)

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    # device (MXU) benchmark: chained bf16 matmuls — LOCAL time only.
    # The reported elapsed must measure THIS host: timing the collective
    # would charge a slow peer's nap to everyone blocked waiting on it
    # (observed: the fast host "became" the straggler).
    # enough timed work that dispatch jitter (a few ms) can't fake a
    # straggler: ~100ms of MXU time on TPU, ~100ms of CPU in tests
    if jax.default_backend() == "tpu":
        size, inner, outer = 2048, 64, 16
    else:
        size, inner, outer = 128, 8, 8
    x = jnp.ones((size, size), dtype=jnp.bfloat16)

    @jax.jit
    def matmul_loop(a):
        def body(_, acc):
            return acc @ a * 0.001 + acc

        return jax.lax.fori_loop(0, inner, body, a)

    # warm-up excludes compile time: every host pays a similar multi-second
    # compile, which drowned the actual execution-speed signal the
    # straggler ratio needs.  hard_block, not block_until_ready: a ready
    # event that resolved at enqueue time would time dispatch latency
    # and blind straggler detection (utils/timing.py).
    from dlrover_tpu.utils.timing import hard_block

    hard_block(matmul_loop(x))
    from dlrover_tpu.timer import get_timer

    start = time.time()
    _mock_slow(envs.get_int(NodeEnv.NODE_ID, default=ctx.process_id))
    with get_timer().span("netcheck_matmul"):
        for _ in range(outer):
            hard_block(matmul_loop(x))
    elapsed = time.time() - start

    # collective benchmark over the group's mesh: psum rides ICI.  Its
    # success/failure feeds fault detection; its latency is shared, so it
    # does not count toward this host's straggler time.
    if ctx.num_processes > 1:
        mesh = Mesh(jax.devices(), ("dp",))
        local = jnp.ones((jax.local_device_count(), 1024), dtype=jnp.float32)
        import numpy as np

        arr = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("dp")), np.asarray(local)
        )

        @jax.jit
        def reduce_loop(a):
            return jnp.sum(a) * jnp.ones(())

        from dlrover_tpu.timer import get_timer

        timer = get_timer()
        for _ in range(4):
            with timer.span("netcheck_psum", timer.KIND_COLLECTIVE):
                hard_block(reduce_loop(arr))

    with open(out_path, "w") as f:
        json.dump({"elapsed": elapsed, "process_id": ctx.process_id}, f)
    return elapsed


if __name__ == "__main__":
    try:
        run_check(sys.argv[1])
    except Exception as e:  # noqa: BLE001
        print(f"node check failed: {e}", file=sys.stderr)
        sys.exit(1)
