"""The windows-and-summaries attention through the mask-operand kernels
(``ops/attention.py::_eva_window_kernels`` over
``ops/pallas/selected_attention.py::masked_attention``) in the interpreter
on the CPU, against the ``jax.numpy`` body it stands in for
(``_eva_window``), float32, so the two agree to rounding; which of the two
``eva_attention`` takes, with the ``attention.path`` event that says so;
that the kernels' two entry points cannot drift; and that the benchmark's
reader, as it stands, takes the kernels' calls."""

import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common
from dlrover_tpu.ops import attention as ops
from dlrover_tpu.ops.pallas import selected_attention as kernels
from dlrover_tpu.ops.pallas.tuning import selected_tiling
from shared_memo import shared_memo

WINDOW, WINDOWS, HEADS, DIM = 256, 3, 2, kernels.KERNEL_HEAD_DIM
TILE = 128   # keys a kernel tile in these cases: a window's keys are two

CASES = {
    # name: positions a chunk.  16 summaries a window do not fill a
    # 128-lane tile: a TPU would keep that shape on jax.numpy
    # (``eva_exact_path``), the interpreter takes it
    "summaries_fill_tiles": 2,
    "sixteen_summaries_a_window": 16,
}
QUANTITIES = ("out", "mass", "q", "k", "v", "mu", "phi")


_window_kernels = ops._eva_window_kernels


@contextlib.contextmanager
def _as_on_a_tpu():
    """``eva_attention`` as a TPU backend would run it, whatever the
    summaries a window, the kernels in the interpreter at tiles of ``TILE``
    keys."""
    def interpreted(*operands, block_kv):
        return _window_kernels(*operands, block_kv=TILE, interpret=True)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(ops, "eva_exact_path", lambda *a: "pallas"), \
            mock.patch.object(ops, "_eva_window_kernels", interpreted):
        yield


def _operands(seed, seq=WINDOW * WINDOWS, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(key, (batch, seq, HEADS, DIM))
               for key in ks[:3])
    mu, phi = (0.2 * jax.random.normal(key, (HEADS, DIM)) for key in ks[3:5])
    return (q, k, v, mu, phi), jax.random.normal(ks[5], q.shape)


@shared_memo
def _both(case):
    """quantity -> (kernels, jax.numpy): the outputs and the gradients of a
    loss that weighs every output element differently."""
    operands, weights = _operands(len(case))

    def run():
        def loss(*xs):
            out, mass, _ = ops.eva_attention(*xs, WINDOW, CASES[case])
            return (out * weights).sum(), (out, mass)

        (_, (out, mass)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(5)), has_aux=True))(*operands)
        return dict(zip(QUANTITIES, (out, mass) + grads))

    want = run()
    with _as_on_a_tpu():
        got = run()
    return {name: (got[name], want[name]) for name in QUANTITIES}


@pytest.mark.parametrize("quantity", QUANTITIES)
@pytest.mark.parametrize("case", CASES)
def test_kernels_against_the_jnp_body(case, quantity):
    got, want = _both(case)[quantity]
    scale = float(jnp.abs(want).max())
    assert scale > 1e-2, "nothing to compare"
    np.testing.assert_allclose(got, want, atol=2e-5 * max(scale, 1.0), rtol=0)


def test_the_first_window_is_plain_causal_attention():
    (q, k, v, _, _), _ = _operands(3, seq=WINDOW)
    none = jnp.zeros((q.shape[0], 0, HEADS, DIM))
    out, mass = ops._eva_window_kernels(
        q, k, v, none, none, block_kv=TILE, interpret=True)
    causal = jnp.tril(jnp.ones((WINDOW, WINDOW), bool))[None, None]
    np.testing.assert_allclose(
        out, ops.reference_attention(q, k, v, causal), atol=2e-6, rtol=0)
    assert float(mass) == 0.0


@pytest.mark.parametrize("backend, window, chunk, head_dim, heads, path", [
    ("cpu", 2048, 16, 128, 32, "jnp"),
    ("tpu", 2048, 16, 64, 32, "jnp"),    # a head is not a 128-lane block
    ("tpu", 2048, 16, 128, 32, "pallas"),   # evabyte_l4.steady's
    ("tpu", 256, 2, 128, 2, "pallas"),
    ("tpu", 256, 16, 128, 2, "jnp"),     # 16 summaries a window: no tile
    ("tpu", 8, 2, 128, 3, "jnp"),        # the tests' tiny windows
])
def test_the_path_follows_backend_and_shape(backend, window, chunk, head_dim,
                                            heads, path):
    assert ops.eva_exact_path(backend, window, chunk, head_dim, heads) == path


def _records(monkeypatch):
    records = []
    monkeypatch.setattr(
        ops.trace, "note_trace_time",
        lambda name, **attrs: records.append((name, attrs)))
    return records


def _event(exact, **more):
    return ("attention.path", dict(
        impl="eva", seq=WINDOW * WINDOWS, window=WINDOW, chunk=2,
        windows=WINDOWS, summaries_max=(WINDOWS - 1) * WINDOW // 2,
        heads=HEADS, head_dim=DIM, exact=exact, **more))


def test_on_the_cpu_the_event_says_jnp(monkeypatch):
    records = _records(monkeypatch)
    ops.eva_attention(*_operands(4, batch=1)[0], WINDOW, 2)
    assert records == [_event("jnp")]


def test_on_a_tpu_the_event_says_pallas_and_its_tile(monkeypatch):
    """Backend and shape alone send the sequence through the kernels, at
    the table's tile, and the result is the ``jax.numpy`` path's."""
    operands, _ = _operands(5, batch=1)
    want = ops.eva_attention(*operands, WINDOW, 2)
    records = _records(monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    called = []

    def interpreted(*a, block_kv):
        called.append(block_kv)
        return _window_kernels(*a, block_kv=block_kv, interpret=True)

    monkeypatch.setattr(ops, "_eva_window_kernels", interpreted)
    got = ops.eva_attention(*operands, WINDOW, 2)
    block_kv = selected_tiling(WINDOW, DIM)[0]
    event, (kept_name, kept) = records
    assert event == _event("pallas", block_kv=block_kv)
    # beside it, what a rematerialised layer keeps of the kernels' calls
    # (tests/test_remat_kept.py holds the bytes to the residuals)
    assert kept_name == "remat.kept" and kept["core"] == "eva"
    assert kept["names"] == "attn_out,attn_lse"
    assert called == [block_kv] * WINDOWS
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=0)


# --------------------------------------------------------------------------
# the kernels' two entry points
# --------------------------------------------------------------------------

def _keye_block(seed=0, queries=512, keys=1024, heads=32, kv_heads=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (1, queries, heads, DIM))
    k = jax.random.normal(ks[1], (1, keys, kv_heads, DIM))
    v = jax.random.normal(ks[2], (1, keys, kv_heads, DIM))
    causal = (jnp.arange(keys - queries, keys)[:, None] >= jnp.arange(keys))
    keep = causal[None] & jax.random.bernoulli(ks[3], 0.5, (1, queries, keys))
    keep = keep.at[:, :, 0].set(True)
    return q, k, v, keep, jax.random.normal(ks[4], q.shape)


ENTRY_QUANTITIES = ("out", "q", "k", "v")


@shared_memo
def _entry_points():
    """quantity -> (``masked_attention``, ``selected_attention``) at one of
    Keye's blocks: 512 queries, 32 heads on 4 kv heads."""
    q, k, v, keep, weights = _keye_block()

    def run(attend):
        def loss(q, k, v):
            out = attend(q, k, v)[0]
            return (out * weights).sum(), out

        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return dict(zip(ENTRY_QUANTITIES, (out,) + grads))

    got = run(lambda q, k, v: kernels.masked_attention(
        q, k, v, keep, None, 512, True))
    want = run(lambda q, k, v: kernels.selected_attention(
        q, k, v, keep, (512, 512), True))
    return {name: (got[name], want[name]) for name in ENTRY_QUANTITIES}


@pytest.mark.parametrize("quantity", ENTRY_QUANTITIES)
def test_the_entry_points_agree_at_keyes_shape(quantity):
    """The same kernels under both, so the same bits."""
    got, want = _entry_points()[quantity]
    assert bool(jnp.any(want))
    np.testing.assert_array_equal(got, want)


def test_the_lse_is_the_log_of_the_row_sums_and_carries_no_gradient():
    q, k, v, keep, _ = _keye_block(1, queries=128, keys=256, heads=4,
                                   kv_heads=2)
    _, lse = kernels.masked_attention(q, k, v, keep, None, TILE, True)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(1, 128, 2, 2, DIM), k)
    scores = jnp.where(keep[:, None, None], scores * DIM ** -0.5, -jnp.inf)
    want = jax.scipy.special.logsumexp(scores, axis=-1).reshape(1, 4, 128)
    np.testing.assert_allclose(lse, want, atol=1e-5, rtol=0)
    grads = jax.grad(
        lambda *x: kernels.masked_attention(
            *x, keep, None, TILE, True)[1].sum(), argnums=(0, 1, 2))(q, k, v)
    assert all(not bool(jnp.any(g)) for g in grads)


SHARED_QUANTITIES = ("out", "lse", "q", "k", "v", "shared_k", "shared_v")


@shared_memo
def _with_shared_keys():
    """A GQA group of two over two tiles of masked keys and 384 keys every
    query attends to, visited as 256 and 128: against dense ``jax.numpy``
    over ``[k ; shared]`` under ``[keep | ones]``."""
    q, k, v, keep, weights = _keye_block(2, queries=128, keys=512, heads=4,
                                         kv_heads=2)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    shared = tuple(jax.random.normal(key, (1, 384, 2, DIM)) for key in ks)

    def dense(q, k, v, shared_k, shared_v):
        keys = jnp.concatenate([k, shared_k], axis=1)
        values = jnp.concatenate([v, shared_v], axis=1)
        allowed = jnp.concatenate(
            [keep, jnp.ones((1, 128, 384), bool)], axis=-1)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk",
                            q.reshape(1, 128, 2, 2, DIM), keys) * DIM ** -0.5
        scores = jnp.where(allowed[:, None, None], scores, -jnp.inf)
        out = jnp.einsum("bgrqk,bkgd->bqgrd",
                         jax.nn.softmax(scores, axis=-1), values)
        lse = jax.scipy.special.logsumexp(scores, axis=-1)
        return out.reshape(q.shape), lse.reshape(1, 4, 128)

    def through_kernels(q, k, v, shared_k, shared_v):
        return kernels.masked_attention(
            q, k, v, keep, (shared_k, shared_v), 256, True)

    def run(attend):
        def loss(*xs):
            out, lse = attend(*xs)
            return (out * weights).sum(), (out, lse)

        (_, results), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(5)), has_aux=True))(q, k, v, *shared)
        return dict(zip(SHARED_QUANTITIES, results + grads))

    got, want = run(through_kernels), run(dense)
    return {name: (got[name], want[name]) for name in SHARED_QUANTITIES}


@pytest.mark.parametrize("quantity", SHARED_QUANTITIES)
def test_keys_every_query_attends_to(quantity):
    got, want = _with_shared_keys()[quantity]
    scale = float(jnp.abs(want).max())
    assert scale > 1e-2, "nothing to compare"
    np.testing.assert_allclose(got, want, atol=2e-5 * max(scale, 1.0), rtol=0)


# --------------------------------------------------------------------------
# the benchmark's reader, as it stands, takes the kernels
# --------------------------------------------------------------------------

def _reader():
    """``benchmarks/layer_metrics/eva_attn_ms_per_step.py``, loaded as the
    benchmark loads it; read, never edited."""
    return common.load_module("layer_metrics", "eva_attn_ms_per_step")


#: ``families/evabyte.py::eva_attn_shape`` of ``evabyte_l4.steady``
CELL = {"batch": 1, "seq": 16384, "window": 2048, "chunk": 16, "windows": 8,
        "heads": 32, "head_dim": 128, "layers": 4}

# A device operation's name in the chip's trace is its whole HLO text.
# These are ``evabyte_l4.steady``'s, from the traced runs of seeds 3600000101
# and 3600000207 (TPU v5 lite, 2026-09-29), cut after the operands' layout constraints;
# ``{n}`` stands where the trace has a window's count of summaries (the
# trace's own text for 128 in the forward and for every count in the
# backward: nothing else of a call's text differs between windows).
T = "{2,1,0:T(8,128)(2,1)}"
FORWARD_FIRST = (
    "%_eva_window_kernels.248 = (bf16[1,2048,4096]{2,1,0:T(8,128)(2,1)S(1)}, "
    "f32[1,32,2048,128]{3,2,1,0:T(8,128)}) custom-call("
    "bf16[1,2048,4096]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.121, "
    "bf16[1,2048,4096]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.120, "
    "bf16[1,2048,4096]{2,1,0:T(8,128)(2,1)S(1)} %copy.1285, "
    "s8[1,2048,2048]{2,1,0:T(8,128)(4,1)} %fusion.834), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={'
    "bf16[1,2048,4096]{2,1,0}, bf16[1,2048,4096]{2,1,0}, "
    "bf16[1,2048,4096]{2,1,0}, s8[1,2048,2048]{2,1,0}}")
FORWARD = (
    "%_eva_window_kernels.249 = (bf16[1,2048,4096]" + T + ", "
    "f32[1,32,2048,128]{3,2,1,0:T(8,128)}) custom-call("
    "bf16[1,2048,4096]" + T + " %copy.1286, "
    "bf16[1,2048,4096]{2,1,0:T(8,128)(2,1)S(1)} %copy.1287, "
    "bf16[1,2048,4096]" + T + " %copy.1288, "
    "s8[1,2048,2048]{2,1,0:T(8,128)(4,1)} %fusion.834, "
    "bf16[1,{n},4096]" + T + " %copy_bitcast_fusion.38, "
    "bf16[1,{n},4096]" + T + " %reshape.3355), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={'
    "bf16[1,2048,4096]{2,1,0}, bf16[1,2048,4096]{2,1,0}, "
    "bf16[1,2048,4096]{2,1,0}, s8[1,2048,2048]{2,1,0}, "
    "bf16[1,{n},4096]{2,1,0}, bf16[1,{n},4096]{2,1,0}}")
BACKWARD_FIRST = (
    "%_eva_window_kernels.247 = (bf16[1,2048,4096]" + T + ", "
    "bf16[1,2048,4096]" + T + ", bf16[1,2048,4096]" + T + ") custom-call("
    "bf16[1,2048,4096]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.79, "
    "bf16[1,2048,4096]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.77, "
    "bf16[1,2048,4096]" + T + " %copy-done.80, "
    "s8[1,2048,2048]{2,1,0:T(8,128)(4,1)} %fusion.769, "
    "bf16[1,2048,4096]" + T + " %copy.1248, "
    "bf16[1,2048,4096]" + T + " %copy-done.81, "
    "f32[1,32,2048,128]{3,2,1,0:T(8,128)} %jit__eva_window_kernels_.265), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={'
    "bf16[1,2048,4096]{2,1,0}, bf16[1,2048,4096]{2,1,0}, "
    "bf16[1,2048,4096]{2,1,0}, s8[1,2048,2048]{2,1,0}, "
    "bf16[1,2048,4096]{2,1,0}, bf16[1,2048,4096]{2,1,0}, "
    "f32[1,32,2048,128]{3,2,1,0}}")
BACKWARD = (
    "%_eva_window_kernels.246 = (bf16[1,2048,4096]" + T + ", "
    "bf16[1,2048,4096]" + T + ", bf16[1,2048,4096]" + T + ", "
    "bf16[1,{n},4096]" + T + ", bf16[1,{n},4096]" + T + ") custom-call("
    "bf16[1,2048,4096]" + T + " %copy.1223, "
    "bf16[1,2048,4096]" + T + " %copy.1224, "
    "bf16[1,2048,4096]" + T + " %copy.1225, "
    "s8[1,2048,2048]{2,1,0:T(8,128)(4,1)} %fusion.769, "
    "bf16[1,2048,4096]" + T + " %copy.1238, "
    "bf16[1,2048,4096]" + T + " %pallas_call.776, "
    "f32[1,32,2048,128]{3,2,1,0:T(8,128)} %jit__eva_window_kernels_.264, "
    "bf16[1,{n},4096]" + T + " %copy-done.127, "
    "bf16[1,{n},4096]" + T + " %reshape.3256), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={'
    "bf16[1,2048,4096]{2,1,0}, bf16[1,2048,4096]{2,1,0}, "
    "bf16[1,2048,4096]{2,1,0}, s8[1,2048,2048]{2,1,0}, "
    "bf16[1,2048,4096]{2,1,0}, bf16[1,2048,4096]{2,1,0}, "
    "f32[1,32,2048,128]{3,2,1,0}, bf16[1,{n},4096]{2,1,0}, "
    "bf16[1,{n},4096]{2,1,0}}")
#: the mask itself, made once a step
MASK = ("%fusion.834 = s8[1,2048,2048]{2,1,0:T(8,128)(4,1)} fusion(), "
        "kind=kLoop, calls=%fused_computation.477.clone.clone")

NOT_THE_WINDOWS = {
    # the softmax mass on summaries: one fusion from the LSE's rows, a
    # window's queries and the summaries to a scalar (same run)
    "mass_on_summaries": (
        "%fusion.848 = f32[]{:T(128)} fusion(f32[32,2048]{1,0:T(8,128)S(1)} "
        "%slice_reduce_fusion.52, bf16[1,2048,32,128]{1,3,2,0:T(8,128)(2,1)"
        "S(1)} %copy-done.33, bf16[1,1024,32,128]{3,1,2,0:T(8,128)(2,1)S(1)} "
        "%copy-done.11), kind=kOutput, calls=%fused_computation.387.clone"),
    "mass_as_an_array_would_be": (
        "%fusion.9 = f32[1,32,2048,{n}]{3,2,1,0:T(8,128)} fusion("
        "bf16[1,2048,32,128]{3,2,1,0} %copy.1, bf16[1,{n},32,128]{3,2,1,0} "
        "%slice.1), kind=kOutput, calls=%fused_computation.9"),
    "lse_rows": (
        "%slice_reduce_fusion.28 = f32[32,2048]{1,0:T(8,128)S(1)} fusion("
        "f32[1,32,2048,128]{3,2,1,0:T(8,128)} %pallas_call.829), kind=kLoop, "
        "calls=%fused_computation.425.clone.clone"),
    "summaries_of_a_window": (
        "%slice.1383 = bf16[1,{n},32,128]{3,2,1,0:T(8,128)(2,1)S(1)} slice("
        "bf16[1,1024,32,128]{3,2,1,0:T(8,128)(2,1)S(1)} %bitcast.1869), "
        "slice={[0:1], [0:{n}], [0:32], [0:128]}"),
    # 2560 = 2048 + 4 x 128 columns: the output head's eight blocks of 320
    "output_head": (
        "%fusion.1 = bf16[16384,2560]{1,0:T(8,128)(2,1)} fusion("
        "bf16[16384,4096]{1,0:T(8,128)(2,1)} %fusion.2, "
        "bf16[4096,2560]{1,0:T(8,128)(2,1)} %copy.3), kind=kOutput, "
        "calls=%fused_computation.1"),
    # an FA2 forward call, as mistral7b_l2.steady's trace has it
    "fa2_forward": (
        "%attn._attend.1 = (bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)}, "
        "f32[2,32,2048,128]{3,2,1,0:T(8,128)}) custom-call("
        "bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)} %fusion.10, "
        "bf16[2,2048,1024]{2,1,0:T(8,128)(2,1)} %fusion.11, "
        "bf16[2,2048,1024]{2,1,0:T(8,128)(2,1)} %fusion.12), "
        'custom_call_target="tpu_custom_call"'),
}


def _calls_of(window):
    """(forward, backward) as the trace prints window ``window``'s."""
    if window == 0:
        return FORWARD_FIRST, BACKWARD_FIRST
    n = str(128 * window)
    return FORWARD.replace("{n}", n), BACKWARD.replace("{n}", n)


@pytest.mark.parametrize("window", range(CELL["windows"]))
def test_the_reader_takes_a_windows_kernel_calls(window):
    """By the window's mask ``s8[1, 2048, 2048]`` among the operands: the
    reader's first rule (a window of queries by ``2048 + 128 j`` keys) at
    ``j = 0``, for every window."""
    reader = _reader()
    for call in _calls_of(window):
        assert 'custom_call_target="tpu_custom_call"' in call
        assert reader.is_window_op(call, CELL)
        assert reader.is_eva_attn_op(call, CELL)
        assert not reader.is_pool_op(call, CELL)


def test_the_reader_takes_the_mask():
    assert _reader().is_window_op(MASK, CELL)


@pytest.mark.parametrize("what", NOT_THE_WINDOWS)
def test_the_reader_leaves_alone(what):
    reader = _reader()
    for window in range(1, CELL["windows"]):
        text = NOT_THE_WINDOWS[what].replace("{n}", str(128 * window))
        assert not reader.is_eva_attn_op(text, CELL), text


def test_the_cell_shape_is_the_familys():
    family = common.load_module("families", "evabyte")
    config = common.read_json(common.HERE, "configs", "evabyte_l4.json")
    assert family.eva_attn_shape(config, 1, 16384) == CELL
