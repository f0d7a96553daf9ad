"""Device scopes (PR 37): every instruction of a compiled step traced back
to the layer that asked for it.

* the program's side (``observability/trace.py``): the kind table on the
  compiled step of a tiny configuration of each family the benchmark's cells
  run; the thunk ``Trainer`` leaves, which no ``train_step`` evaluates;
* the reader's side (``benchmarks/device_scopes.py``): its arithmetic on
  plain lists, and the whole of it on the tree's recorded chip trace
  (``benchmarks/tests/data/tiny_step.xplane.pb``, a "TPU v5 lite") with a
  map built from the ``op_name`` each of that trace's instructions carries.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import device_scopes
from benchmarks import trace as trace_mod
from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from dlrover_tpu.models.moe import MoELlamaConfig
from dlrover_tpu.observability import jitscope, trace
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.train import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
XPLANE = os.path.join(ROOT, "benchmarks", "tests", "data",
                      "tiny_step.xplane.pb")
SEQ = 32

#: kinds every family has, and what each adds
COMMON = {"embed", "norm", "attn.proj", "attn.core", "head_loss", "optimizer"}
FAMILIES = {
    "llama_dense": (
        lambda: LlamaForCausalLM(LlamaConfig.tiny()),
        COMMON | {"mlp"}, {}),
    "gpt": (
        lambda: GPT(GPTConfig.tiny()), COMMON | {"mlp"}, {}),
    "olmoe_block": (
        lambda: LlamaForCausalLM(MoELlamaConfig.tiny_moe(qk_norm=True)),
        COMMON | {"moe"},
        {"moe": {"route", "sort", "gmm", "combine"}}),
    "keye_indexer": (
        lambda: LlamaForCausalLM(MoELlamaConfig.tiny_moe(
            num_experts=8, top_k=3, experts_held=2, first_expert=4,
            norm_topk_prob=True, qk_norm="head", index_topk=8,
            index_heads=2, index_head_dim=8, index_block=8, max_seq_len=SEQ)),
        COMMON | {"moe"},
        {"attn.core": {"scores", "select", "selected", "index_loss"},
         "moe": {"route", "sort", "gmm", "combine"}}),
    "evabyte_windows": (
        lambda: LlamaForCausalLM(LlamaConfig.tiny(
            num_kv_heads=4, eva_window=8, eva_chunk=2, pred_heads=4,
            residual_dtype=jnp.float32, norm_unit_offset=True)),
        COMMON | {"mlp"},
        {"attn.core": {"pool", "windows", "summary_mass"}}),
    "ling_latent": (
        lambda: LlamaForCausalLM(MoELlamaConfig.tiny_moe(
            num_layers=3, layer_prefix=("kda:dense",),
            layer_pattern=("kda", "mla"), dense_intermediate_size=96,
            kda_heads=2, kda_head_dim=16, kda_chunk=16,
            kda_full_rank_gates=True, kda_decay_lower_bound=-5.0,
            kda_neg_eigval=False, mla_kv_rank=24, mla_nope_dim=16,
            mla_rope_dim=8, mla_v_dim=16, mla_head_gate=True,
            num_experts=16, top_k=4, experts_held=4, norm_topk_prob=True,
            router_scores="sigmoid", shared_experts=1, n_group=4,
            topk_group=2, selection_bias=True, max_seq_len=SEQ)),
        COMMON | {"moe", "mlp"},
        {"attn.core": {"latent", "conv", "decay", "chunk", "state", "gate"},
         "attn.proj": {"latent"}, "optimizer": {"bias"},
         "moe": {"route", "sort", "gmm", "combine", "shared"}}),
    "laguna_window": (
        lambda: LlamaForCausalLM(MoELlamaConfig.tiny_moe(
            num_layers=3, layer_prefix=("gqa:dense",),
            layer_pattern=("swa", "gqa"), dense_intermediate_size=96,
            num_heads=6, swa_heads=8, num_kv_heads=2, sliding_window=8,
            swa_rope_theta=10000.0, rope_theta=500000.0,
            partial_rotary_factor=0.5, yarn_factor=64.0,
            yarn_original_max_len=64, yarn_beta_fast=8.0,
            attn_head_gate=True, num_experts=16, top_k=4, experts_held=4,
            norm_topk_prob=True, router_scores="sigmoid", shared_experts=1,
            routed_scaling_factor=2.5, max_seq_len=SEQ)),
        COMMON | {"moe", "mlp"},
        {"attn.core": {"window"},
         "moe": {"route", "sort", "gmm", "combine", "shared"}}),
    "ouro_loop": (
        lambda: LlamaForCausalLM(LlamaConfig.tiny(
            loop_steps=4, sandwich_norm=True, exit_gate=True,
            exit_entropy_weight=0.05)),
        COMMON | {"mlp"}, {"head_loss": {"exit"}}),
    "nemotron_one_branch": (
        lambda: LlamaForCausalLM(MoELlamaConfig.tiny_moe(
            num_layers=5, layer_pattern=("ffn", "mamba2:alone"),
            layer_suffix=("gqa:alone",), use_rope=False, num_kv_heads=1,
            mamba2_heads=4, mamba2_head_dim=8, mamba2_groups=2,
            mamba2_state=16, mamba2_chunk=8, num_experts=8, top_k=3,
            experts_held=4, intermediate_size=24, moe_latent_size=16,
            mlp_matrices=2, mlp_activation="relu2", shared_experts=1,
            shared_intermediate_size=48, norm_topk_prob=True,
            router_scores="sigmoid", routed_scaling_factor=5.0,
            selection_bias=True, max_seq_len=SEQ)),
        COMMON | {"moe"},
        {"attn.core": {"conv", "decay", "ssd", "gate"},
         "optimizer": {"bias"},
         "moe": {"route", "sort", "gmm", "combine", "shared", "latent"}}),
    "lfm2_conv": (
        lambda: LlamaForCausalLM(MoELlamaConfig.tiny_moe(
            num_layers=5, layer_prefix=("conv:dense",),
            layer_pattern=("gqa", "conv", "conv", "conv"),
            dense_intermediate_size=96, qk_norm="head", tie_embeddings=True,
            num_experts=16, top_k=4, experts_held=4, norm_topk_prob=True,
            norm_topk_eps=1e-6, router_scores="sigmoid",
            selection_bias=True, max_seq_len=SEQ)),
        COMMON | {"moe", "mlp"},
        {"attn.core": {"gconv"}, "optimizer": {"bias"},
         "moe": {"route", "sort", "gmm", "combine"}}),
}

#: a path may be ``other`` where it names nothing but the layer stack and
#: the transformations around it: the residual adds, the scan's slicing of
#: the stacked parameters, the positions and the causal mask
STACK = {"layers", "layer", "h", "block", "jit(wrapped)", "LlamaForCausalLM",
         "GPT", "while", "body", "cond", "closed_call", "checkpoint",
         "rematted_computation", "jit(tril)",
         # a pattern's runs (``ling_latent``)
         "prefix", "kda_dense_0", "kda_0", "mla_1",
         # (``laguna_window``)
         "gqa_dense_0", "swa_0", "gqa_1",
         # a looped stack's scan over loop steps (``ouro_loop``)
         "LlamaForCausalLM.loop_step", "LlamaForCausalLM._looped_stack",
         # layers of one branch and the suffix (``nemotron_one_branch``)
         "suffix", "ffn_0", "mamba2_alone_1", "gqa_alone_0",
         # a dense ``conv`` layer, then (softmax, three ``conv`` layers)
         # (``lfm2_conv``)
         "conv_dense_0", "gqa_0", "conv_1"}

def _step_text(model, steps=0):
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    trainer = Trainer(model, optax.adamw(1e-3), mesh,
                      grads_dtype=jnp.bfloat16)
    ids = np.random.default_rng(0).integers(0, 256, size=(2, SEQ + 1))
    batch = {"input_ids": np.asarray(ids[:, :-1], np.int32),
             "labels": np.asarray(ids[:, 1:], np.int32)}
    state = trainer.create_state(jax.random.PRNGKey(0), batch["input_ids"])
    for _ in range(steps):
        state, _ = trainer.train_step(state, trainer.shard_batch(batch))
    return trainer, trainer.lower_train_step(
        state, trainer.shard_batch(batch)).compile().as_text()


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    build, kinds, subs = FAMILIES[request.param]
    _, text = _step_text(build())
    return text, kinds, subs


class TestKindsOfACompiledStep:
    def test_every_kind_the_family_has_is_present(self, family):
        text, kinds, subs = family
        found = trace.parse_device_scopes(text).scopes.values()
        assert kinds <= {kind for kind, _, _ in found}
        for kind, wanted in subs.items():
            assert wanted <= {sub for k, sub, _ in found if k == kind}

    def test_nothing_named_falls_to_other_but_the_layer_stack(self, family):
        text = family[0]
        strays = set()
        for op_name in re.findall(r'op_name="([^"]*)"', text):
            # a looped stack's heads, forward walk and backward rule alike
            assert ("_sow_exit_objective" not in op_name
                    or trace.scope_of(op_name)[0] == "head_loss")
            if trace.scope_of(op_name)[0] != trace.OTHER:
                continue
            if not {trace.unwrapped(part)
                    for part in op_name.split("/")[:-1]} <= STACK:
                strays.add(op_name)
        assert not strays

    def test_pass_tells_forward_from_remat_from_backward(self, family):
        text = family[0]
        found = trace.parse_device_scopes(text).scopes.values()
        for kind in ("attn.proj", "norm"):      # inside the remat layer
            assert {which for k, _, which in found if k == kind} == {
                trace.FORWARD, trace.REMAT, trace.BACKWARD}
        # a core with a backward rule of its own (``ops/short_conv.py``):
        # the rule's passes carry the core's scope too
        if "gconv" in family[2].get("attn.core", ()):
            assert {which for k, sub, which in found
                    if (k, sub) == ("attn.core", "gconv")} == {
                trace.FORWARD, trace.REMAT, trace.BACKWARD}
        # the optimizer's pass is differentiated by nobody
        assert {which for k, _, which in found if k == "optimizer"} == {
            trace.FORWARD}


@pytest.mark.parametrize("op_name, want", [
    ("jit(wrapped)/jvp(LlamaForCausalLM)/while/body/closed_call/layers/layer/"
     "attn/attn.core/attn._attend/pallas_call",
     ("attn.core", "", "forward")),
    ("jit(wrapped)/transpose(jvp(LlamaForCausalLM))/while/body/closed_call/"
     "checkpoint/rematted_computation/layers/layer/attn/attn.core/"
     "attn._attend/pallas_call", ("attn.core", "", "remat")),
    ("jit(wrapped)/transpose(jvp(LlamaForCausalLM))/while/body/closed_call/"
     "checkpoint/layers/layer/attn/q_proj/dot_general",
     ("attn.proj", "", "backward")),
    ("jit(wrapped)/jvp(LlamaForCausalLM)/while/body/closed_call/layers/layer/"
     "mlp/moe/mlp._experts/shard_map/moe/exchange/all_gather",
     ("moe", "exchange", "forward")),
    ("jit(wrapped)/jvp(head_loss)/jit(take_along_axis)/gather",
     ("head_loss", "", "forward")),
    ("jit(wrapped)/transpose(jvp(head_loss))/add_any",
     ("head_loss", "", "backward")),
    ("jit(wrapped)/optimizer/mul", ("optimizer", "", "forward")),
    ("jit(wrapped)/jvp(LlamaForCausalLM)/while/body/closed_call/layers/layer/"
     "attn/attn._attend_indexed/index_q_proj/dot_general",
     ("attn.proj", "", "forward")),
    ("jit(wrapped)/jvp(LlamaForCausalLM)/while/body/closed_call/layers/layer/"
     "attn/attn._attend_eva/attn.core/windows/jit(_eva_window_kernels)/"
     "summary_mass/exp", ("attn.core", "summary_mass", "forward")),
    ("jit(wrapped)/jvp(LlamaForCausalLM)/while/body/closed_call/layers/layer/"
     "add", ("other", "", "forward")),
    ("a/jit(mlp)/mul", ("other", "", "forward")),   # a function's name
    # a fusion's merged paths: the first is its root's
    ("jit(wrapped)/jvp(GPT)/while/body/closed_call/h/block/attn/attn.core/"
     "block._attend/reshape;h/block/attn/squeeze",
     ("attn.core", "", "forward")),
    ("select/reduce_max", ("attn.core", "select", "forward")),
    ("ragged-dot-none", ("moe", "gmm", "forward")),
    ("reduce_sum", ("unnamed", "", "")),
    ("state.params['embed_tokens'].value", ("unnamed", "", "")),
    ("", ("unnamed", "", "")),
])
def test_scope_of(op_name, want):
    assert trace.scope_of(op_name) == want


def test_parse_names_operands_and_users():
    text = "\n".join([
        "HloModule jit_wrapped",
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        '  ROOT %m = f32[4]{0} multiply(%p, %p), metadata={op_name='
        '"jit(wrapped)/optimizer/mul"}',
        "}",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        "  %a = f32[4]{0} parameter(0)",
        "  %copy.1 = f32[4]{0:T(128)} copy(%a)",
        "  %fusion.1 = f32[4]{0} fusion(%copy.1), kind=kLoop, "
        'calls=%fused_computation.1, metadata={op_name='
        '"jit(wrapped)/optimizer/mul" stack_frame_id=3}',
        "  ROOT copy.2 = (f32[4]{0}, f32[4]{0}) tuple(%fusion.1, %a)",
        "}"])
    found = trace.parse_device_scopes(text)
    assert found.scopes["%fusion.1"] == ("optimizer", "", "forward")
    assert found.scopes["%copy.1"][0] == trace.UNNAMED
    assert found.first_operand["%fusion.1"] == "%copy.1"
    assert found.users["%copy.1"] == ("%fusion.1",)
    assert found.users["%a"] == ("%copy.1", "%copy.2")
    assert "%copy.2" in found.scopes        # a name printed without its %


class TestTheThunkCostsAStepNothing:
    def test_stored_once_and_evaluated_by_no_step(self, monkeypatch):
        left = []
        real = trace.register_device_scopes
        monkeypatch.setattr(
            trace, "register_device_scopes",
            lambda name, thunk: (left.append(name), real(name, thunk)))
        jitscope.install()
        trainer, _ = _step_text(LlamaForCausalLM(LlamaConfig.tiny()), steps=3)
        assert left == ["trainer.step"]
        assert "trainer.step" not in trace._scope_maps
        before = jitscope.totals()
        found = trace.device_scopes("trainer.step")
        after = jitscope.totals()
        # the executable JAX already holds: nothing compiled again, no
        # lookup in the persistent cache
        assert (after["hits"], after["misses"]) == (
            before["hits"], before["misses"])
        assert after["compile_s"] - before["compile_s"] < 0.5
        assert {"optimizer", "attn.core", "mlp"} <= {
            kind for kind, _, _ in found.scopes.values()}
        assert trace.device_scopes("trainer.step") is found     # memoised

    def test_nothing_registered_reads_none(self):
        assert trace.device_scopes("no.such.program") is None


class TestReaderArithmetic:
    def test_self_time_under_nested_containers(self):
        ops = [("%while.1", 0.0, 10.0), ("%fusion.1", 1.0, 3.0),
               ("%conditional.2", 3.0, 8.0), ("%fusion.2", 4.0, 5.0),
               ("%call.1", 5.0, 7.5), ("%fusion.3", 5.5, 6.0),
               ("%fusion.4", 12.0, 13.0)]
        got = dict(device_scopes.self_times(ops))
        assert got == {"%while.1": 3.0, "%fusion.1": 2.0,
                       "%conditional.2": 1.5, "%fusion.2": 1.0,
                       "%call.1": 2.0, "%fusion.3": 0.5, "%fusion.4": 1.0}
        assert sum(got.values()) == trace_mod.total(
            trace_mod.union([(s, e) for _, s, e in ops]))

    def test_an_operation_that_outlasts_its_container_keeps_the_rest(self):
        ops = [("%a", 0.0, 4.0), ("%b", 3.0, 6.0)]
        assert dict(device_scopes.self_times(ops)) == {"%a": 3.0, "%b": 3.0}

    def test_whole_steps_by_the_outermost_loop(self):
        ops = []
        for step in range(3):
            t = 10.0 * step
            ops += [("%fusion.9 = f32[] fusion()", t, t + 1),
                    ("%while.1 = () while()", t + 1, t + 8)]
            ops += [(f"%while.7 = () while()", t + 2 + i, t + 2.5 + i)
                    for i in range(4)]
        assert device_scopes.whole_steps(ops) == (1.0, 21.0, 2)
        # no loop: the instruction with the fewest runs
        flat = [op for op in ops if not op[0].startswith("%while")]
        assert device_scopes.whole_steps(flat) == (0.0, 20.0, 2)
        assert device_scopes.whole_steps(flat[:1]) is None

    def test_one_hop_inheritance(self):
        scopes = {
            "%copy.1": ("unnamed", "", "forward"),      # one user
            "%copy.2": ("unnamed", "", "forward"),      # two users: operand
            "%copy.3": ("unnamed", "", "forward"),      # neighbours unnamed
            "%copy.4": ("unnamed", "", "forward"),      # two hops away
            "%fusion.1": ("mlp", "", "backward"),
            "%fusion.2": ("norm", "", "forward"),
        }
        first = {"%copy.1": "%fusion.2", "%copy.2": "%fusion.2",
                 "%copy.3": "%copy.4", "%copy.4": "%copy.1"}
        users = {"%copy.1": ("%fusion.1",),
                 "%copy.2": ("%fusion.1", "%copy.3"),
                 "%copy.4": ("%copy.3",)}
        got = device_scopes.resolve(scopes, first, users)
        assert got["%copy.1"] == ("mlp", "", "backward", True)
        assert got["%copy.2"] == ("norm", "", "forward", True)
        assert got["%copy.3"] == ("unnamed", "", "forward", False)
        assert got["%copy.4"] == ("unnamed", "", "forward", False)
        assert got["%fusion.1"] == ("mlp", "", "backward", False)

    def test_the_partition_sums_to_busy_time(self):
        ops = []
        for step in range(3):
            t = 1.0 * step
            ops += [("%while.1 = () while()", t, t + 0.7),
                    ("%fusion.1 = f32[] fusion()", t + 0.1, t + 0.3),
                    ("%copy.1 = f32[] copy()", t + 0.3, t + 0.4),
                    ("%fusion.2 = f32[] fusion()", t + 0.75, t + 0.95),
                    ("%fusion.5 = f32[] fusion()", t + 0.95, t + 0.97)]
        resolved = {"%while.1": ("other", "", "forward", False),
                    "%fusion.1": ("attn.core", "windows", "forward", False),
                    "%copy.1": ("attn.core", "", "forward", True),
                    "%fusion.2": ("optimizer", "", "forward", False)}
        table = device_scopes.cover(ops, resolved)
        assert table["steps"] == 2 and table["unmatched"] == 1
        kinds = device_scopes.by_kind(table)
        assert kinds == pytest.approx({
            "other": 400.0, "attn.core": 300.0, "optimizer": 200.0,
            "unnamed": 20.0})
        assert table["busy_ms"] == pytest.approx(920.0)
        assert table["union_ms"] == pytest.approx(920.0)
        assert table["period_ms"] == pytest.approx(1000.0)
        assert table["unnamed_before_ms"] == pytest.approx(120.0)
        assert table["rows"][("attn.core", "", "forward")] == pytest.approx(
            [100.0, 1.0, 100.0])


# -- the reader on the tree's chip trace -----------------------------------

def _fields(buf):
    """(field number, value) of one protobuf message: varints as ints,
    length-delimited fields as bytes."""
    at, size = 0, len(buf)

    def varint():
        nonlocal at
        value = shift = 0
        while True:
            byte = buf[at]
            at += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                return value

    while at < size:
        key = varint()
        number, wire = key >> 3, key & 7
        if wire == 0:
            yield number, varint()
        elif wire == 2:
            length = varint()
            yield number, buf[at:at + length]
            at += length
        else:
            width = {1: 8, 5: 4}[wire]
            yield number, buf[at:at + width]
            at += width


def _op_names(path, plane_name="/device:TPU:0"):
    """``{instruction: op_name}`` of one device plane of an ``.xplane.pb``:
    the ``tf_op`` stat of each event's metadata (``jax.profiler.ProfileData``
    shows an event's name and times, not these)."""
    with open(path, "rb") as f:
        space = f.read()
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = None, [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 4:
                events.append(dict(_fields(value))[2])
            elif field == 5:
                meta = dict(_fields(dict(_fields(value))[2]))
                stat_names[meta[1]] = bytes(meta.get(2, b"")).decode()
        if name != plane_name:
            continue
        out = {}
        for event in events:
            text, op_name = "", ""
            for field, value in _fields(event):
                if field == 2:
                    text = bytes(value).decode()
                elif field == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == "tf_op" and 5 in stat:
                        op_name = bytes(stat[5]).decode()
            out[text.partition(" = ")[0]] = op_name.rpartition(":")[0]
        return out
    return {}


class TestReaderOnTheRecordedChipTrace:
    @pytest.fixture(scope="class")
    def recorded(self):
        loaded = trace_mod.load(XPLANE)
        op_names = _op_names(XPLANE)
        scopes = {name: trace.scope_of(op_name)
                  for name, op_name in op_names.items()}
        lo, hi = trace_mod.window_of(loaded)
        ops = [op for op in loaded.device_ops[0]
               if op[1] >= lo and op[2] <= hi]
        return ops, op_names, scopes

    def test_every_traced_instruction_is_in_the_map(self, recorded):
        ops, op_names, _ = recorded
        assert {text.partition(" = ")[0] for text, _, _ in ops} <= set(
            op_names)

    def test_the_kernels_by_their_path(self, recorded):
        """The recording is of PR 24's program: ``attn._attend`` stood under
        ``attn`` with no ``attn.core`` around it."""
        _, op_names, scopes = recorded
        kernels = {name: scopes[name] for name, op_name in op_names.items()
                   if op_name.endswith("attn._attend/pallas_call")}
        assert sorted(which for _, _, which in kernels.values()) == [
            "backward", "backward", "forward", "remat"]
        assert {kind for kind, _, _ in kernels.values()} == {"attn.proj"}
        assert all("_attend" in name for name in kernels)

    def test_the_cover_sums_to_the_busy_time(self, recorded):
        ops, _, scopes = recorded
        table = device_scopes.cover(
            ops, device_scopes.resolve(scopes, {}, {}))
        assert table["steps"] == 2 and table["unmatched"] == 0
        lo, hi, n = device_scopes.whole_steps(ops)
        busy = 1e3 * trace_mod.total(trace_mod.clip(trace_mod.union(
            [(s, e) for _, s, e in ops]), (lo, hi))) / n
        assert table["busy_ms"] == pytest.approx(busy, rel=1e-9)
        kinds = device_scopes.by_kind(table)
        assert sum(kinds.values()) == pytest.approx(table["busy_ms"])
        # what PR 24's program had named, and what it had not: the
        # optimizer's pass at the top level of the step, the layout copies
        named = 1 - kinds["unnamed"] / table["busy_ms"]
        assert 0.55 < named < 0.9
        assert {"attn.proj", "mlp", "norm", "head_loss"} <= set(kinds)
        assert "optimizer" not in kinds

    def test_the_kernels_self_time_is_the_by_shape_readers(self, recorded):
        ops, op_names, scopes = recorded
        table = device_scopes.cover(
            ops, device_scopes.resolve(scopes, {}, {}))
        lo, hi, n = device_scopes.whole_steps(ops)
        by_name = 1e3 * sum(
            min(e, hi) - max(s, lo) for text, s, e in ops
            if "_attend" in text.partition(" = ")[0]
            and min(e, hi) > max(s, lo)) / n
        attn_proj = sum(row[0] for (kind, _, _), row in table["rows"].items()
                        if kind == "attn.proj")
        assert 0 < by_name < attn_proj
