"""Llama-family decoder, TPU-first.

The flagship model of the framework (the reference delegates model math to
torch+Megatron; here the model is in-tree and mesh-native).  Design notes:

* every weight and activation carries *logical* axis names via
  ``nn.with_logical_partitioning`` / ``nn.with_logical_constraint``; the
  parallel layer (``dlrover_tpu.parallel.sharding``) maps them onto the
  dp/fsdp/tp/cp/ep mesh — GSPMD inserts all collectives;
* bf16 compute on the MXU, fp32 master params and fp32 softmax/logits;
* layers are ``nn.scan``-stacked (one trace regardless of depth) and
  ``nn.remat``-checkpointed to trade FLOPs for HBM: the forward pass keeps
  a layer's input and, where the attention core runs Pallas kernels that
  name their results (``ops/pallas/kept.py``: the mask-operand attention's
  ``out`` and its LSE as ``[B, H, Q]`` float32, the gated delta rule's
  chunk and state results), those, and of a routed feed-forward the two
  products of its first grouped matmuls (``models/moe.py``), and of the
  dense SwiGLU its gate and up products where those of all layer
  applications fit 1/24 of the device's memory (``kept.py``'s
  ``keeps_mlp_products``, read from the rows a chip holds, the width, the
  layers and the device; elsewhere the dense layer names nothing); the
  backward pass recomputes everything else ``jax.numpy`` computes in the
  layer (norms, projections, RoPE, masks, searches, the experts' sort,
  gathers and activation) and runs no forward kernel whose results were
  kept.
  The FA2 kernel names its ``out`` and LSE over a long stream of keys
  (``ops/pallas/flash_attention.py::backward_path``: at least 16,384, no
  window, a head a block) and nothing over a shorter one, and every
  ``jax.numpy`` core names nothing: their layers are recomputed whole;
* a looped stack (``loop_steps``) is one more ``nn.scan`` AROUND the scan
  over layers, its parameters broadcast: a weight is in the tree once and
  used once a loop step, what the forward pass keeps is every layer
  application's input (and what its core names); a loop step hands out its
  normed stream and its gate's logits, and the heads of its exits run
  AFTER the loop, by blocks of rows (``weighted_token_losses``: no ``[B, S,
  vocab]`` array stands in a training step) and with their gradient taken
  in their forward pass, so that a block's logits are made once;
* attention is GQA with rotary embeddings; the inner kernel is pluggable
  (jnp reference path here, Pallas flash/ring attention in
  ``dlrover_tpu.ops``).
"""

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.observability import trace
from dlrover_tpu.ops.pallas import kept
from dlrover_tpu.ops.pallas.kept import LAYER_POLICY

Dtype = Any


#: ``mlp_activation`` -> the function
ACTIVATIONS = {"silu": nn.silu, "relu2": lambda t: jnp.square(nn.relu(t))}


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    scan_layers: bool = True
    attention_impl: str = "reference"  # reference | flash | ring
    # q and k each RMS-normalised before RoPE: ``True`` over their whole
    # projected width, before the split into heads (OLMoE,
    # arXiv:2409.02060); ``"head"`` over each head's ``head_dim``, one
    # learned scale for q and one for k (Qwen3's, Keye-VL-2.0's)
    qk_norm: Any = False
    # a learned sparse-attention indexer (DeepSeek-V3.2-Exp's lightning
    # indexer; Keye-VL-2.0's ``sa_config``): ``index_heads`` query heads of
    # ``index_head_dim`` over one shared key head score every earlier key,
    # and a query attends to the ``index_topk`` highest.  0: none, every
    # earlier key.  ``index_block`` queries are worked at a time
    index_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    index_block: int = 512
    # EVA attention (arXiv:2302.04542 as EvaByte's model code has it): a
    # query sees the keys of its own window of ``eva_window`` positions
    # exactly and every earlier window through one learned summary of each
    # ``eva_chunk`` positions, under one softmax.  0: none
    eva_window: int = 0
    eva_chunk: int = 0
    # the norms' learned scale is ``1 + g`` with ``g`` starting at 0
    # (EvaByte's ``norm_add_unit_offset``)
    norm_unit_offset: bool = False
    # the dtype the residual stream is kept and added in; ``None``: ``dtype``
    # (EvaByte's ``fp32_skip_add``: float32 beside bfloat16 matmuls)
    residual_dtype: Any = None
    # prediction heads in the one output projection: head ``i`` at position
    # ``t`` predicts token ``t + 1 + i``; the model returns the first head's
    # logits and sows the others' loss (EvaByte's ``num_pred_heads``)
    pred_heads: int = 1
    # the kinds of layer of one period of the stack, repeated to
    # ``num_layers``: ``"gqa"`` (this file's softmax attention), ``"swa"``
    # (the same under a causal window, with numbers of its own: below),
    # ``"kda"`` (a gated delta-rule layer, ``DeltaAttention``) or ``"mla"``
    # (latent attention, ``LatentAttention``).  Empty: one kind, the softmax
    # attention, and the parameter tree ``layers/layer`` as ever.  An entry
    # ``"<kind>:dense"`` is a layer of that kind whose feed-forward is the
    # dense SwiGLU of ``dense_intermediate_size`` whatever
    # ``feed_forward()`` names (a routed model's leading dense layers).  A
    # layer of ONE branch (Nemotron-H's: one norm, ``x + branch(norm(x))``):
    # ``"<kind>:alone"``, the mixer with no feed-forward, and ``"ffn"``
    # (``"ffn:dense"``), the feed-forward with no mixer; its tree holds
    # ``input_norm`` and the one branch, nothing else
    layer_pattern: Tuple[str, ...] = ()
    # layers that stand ONCE before the periods (entries as the pattern's),
    # counted in ``num_layers``; their parameters under ``prefix/<run>``
    layer_prefix: Tuple[str, ...] = ()
    dense_intermediate_size: int = 0
    # softmax attention without positions (``use_rope`` false) and with an
    # elementwise sigmoid gate on its output before the output projection
    # (arXiv:2505.06708; Solar-Open2's ``use_gqa_gate``)
    use_rope: bool = True
    attn_gate: bool = False
    # the same gate a HEAD: ``o_h * sigmoid(h w_g)_h``, ``w_g`` hidden x
    # heads (that paper's head-wise form, ``mla_head_gate``'s), on ``gqa``
    # and ``swa`` layers alike
    attn_head_gate: bool = False
    # a ``swa`` layer: this file's softmax attention in which a query sees
    # itself and the ``sliding_window - 1`` positions before it, with
    # ``swa_heads`` query heads (0: ``num_heads``) over the same
    # ``num_kv_heads`` and plain RoPE over the whole head at base
    # ``swa_rope_theta`` (0: ``rope_theta``).  A kind that differs from
    # ``gqa`` only in numbers: ``attention_numbers``
    sliding_window: int = 0
    swa_heads: int = 0
    swa_rope_theta: float = 0.0
    # RoPE of a ``gqa`` layer on the FIRST ``partial_rotary_factor`` of each
    # head's columns, the rest unrotated (Hugging Face's key of that name)
    partial_rotary_factor: float = 1.0
    # YaRN on a ``gqa`` layer's RoPE (arXiv:2309.00071 as ``transformers``'
    # ``_compute_yarn_parameters`` has it; static, at every length): the
    # frequencies of the rotary columns blended between ``f`` and ``f /
    # yarn_factor`` by a ramp between the pairs that turn ``yarn_beta_fast``
    # and ``yarn_beta_slow`` times in ``yarn_original_max_len`` positions,
    # cos and sin times ``yarn_attention_factor`` (0: ``0.1 ln(factor) +
    # 1``).  ``yarn_factor`` 0: none
    yarn_factor: float = 0.0
    yarn_original_max_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 0.0
    # a ``kda`` layer (Kimi Delta Attention, arXiv:2510.26692): heads of
    # ``kda_head_dim`` keys and values, a causal depthwise convolution of
    # ``kda_conv`` taps on q, k and v, ``kda_chunk`` positions a chunk of
    # ``ops/linear_attention.py::kda``; the decay's and the output gate's
    # low-rank projections have rank ``kda_head_dim``
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_chunk: int = 64
    # the decay's and the output gate's projections at full rank, hidden ->
    # heads x ``kda_head_dim`` (``f_proj``, ``g_proj``; Ling-3.0's
    # ``no_kda_lora``) in place of the two low-rank pairs
    kda_full_rank_gates: bool = False
    # the log decay bounded below: ``g = bound * sigmoid(exp(A_log) (f +
    # dt_bias))`` in ``(bound, 0)`` (fla's ``safe_gate`` with
    # ``lower_bound``; -5 in Ling-3.0) in place of ``-exp(A_log)
    # softplus(..)``.  0: unbounded
    kda_decay_lower_bound: float = 0.0
    # ``beta = 2 sigmoid(..)`` (negative eigenvalues allowed) or, false,
    # ``sigmoid(..)``
    kda_neg_eigval: bool = True
    # an ``mla`` layer (DeepSeek-V2's latent attention, arXiv:2405.04434,
    # without a query bottleneck): ``num_heads`` query heads of
    # ``mla_nope_dim + mla_rope_dim``; keys and values from one latent of
    # ``mla_kv_rank`` a token (RMS-normalised) through an up-projection to
    # ``mla_nope_dim + mla_v_dim`` a head; ONE rotary key of
    # ``mla_rope_dim`` a token that every head shares; RoPE (``rope_theta``)
    # on it and on each head's last ``mla_rope_dim`` query columns; scores
    # over ``sqrt(mla_nope_dim + mla_rope_dim)``; ``mla_head_gate``: the
    # output times ``sigmoid(h w_gate)`` a HEAD before the output projection
    mla_kv_rank: int = 0
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64
    mla_v_dim: int = 128
    mla_head_gate: bool = False
    # the rotary part of an ``mla`` layer turns NEIGHBOURING columns,
    # ``(x[2i], x[2i+1])`` at ``f_i = theta^(-2i/rope)`` (DeepSeek-V3's
    # checkpoint layout, Hugging Face's ``rope_interleave``), where false
    # turns column ``i`` with column ``i + rope/2``
    mla_rope_interleave: bool = False
    # training by diffusion over blocks (BD3-LMs, arXiv:2503.09573; SDAR,
    # arXiv:2510.06303): the length of a block, 0 for none.  The model then
    # runs ``[noisy copy ; clean copy]`` of its input, ``2S`` rows at the
    # positions ``0..S-1`` twice, under the block-diffusion mask
    # (``ops/attention.py::block_diffusion_attention``), returns the noisy
    # half's logits and sows its own objective, the NELBO over the tokens
    # ``noise_blocks`` masked (to ``mask_token_id``, at rates from
    # ``noise_eps`` to 1 a block), from the key of ``step_rngs``
    block_diffusion: int = 0
    mask_token_id: int = 0
    noise_eps: float = 1e-3
    noise_seed: int = 0
    # the norms: ``"rms"`` (``RMSNorm``) or ``"layer"`` (``LayerNorm``: mean
    # taken off, a learned scale and bias; eps ``rms_norm_eps``)
    norm: str = "rms"
    # the output head reads the embedding table (logits ``x E^T``): one
    # table in the tree, no ``lm_head`` (``pred_heads`` 1 alone)
    tie_embeddings: bool = False
    # biases on a softmax layer's q, k, v and output projections
    attention_bias: bool = False
    # differential attention in every ``gqa``, ``swa`` and ``xattn`` layer
    # (arXiv:2410.05258 in the head-paired form of Phi-4-mini-flash's model
    # code): query heads ``(2j, 2j+1)`` are ``q1_j, q2_j``, key heads ``(2m,
    # 2m+1)`` ``k1_m, k2_m``, value heads ``(2m, 2m+1)`` side by side ``V_m``
    # of ``2 head_dim``; ``O_j = (softmax(q1 k1^T) - lambda softmax(q2
    # k2^T)) V_m`` under the layer's mask, RMS-normalised over its ``2
    # head_dim`` (a learned scale) and times ``1 - lambda_init``; ``lambda =
    # exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 -
    # 0.6 exp(-0.3 i)`` at layer ``i`` of the stack
    diff_attention: bool = False
    # a ``mamba`` layer (Mamba-1's mixer, arXiv:2312.00752): ``[a | z] = h
    # W_in`` of ``mamba_expand * hidden_size`` each; ``a = silu(conv(a) +
    # b)``, causal and depthwise over ``mamba_conv`` taps; ``[r | B | C] = a
    # W_x`` (``mamba_dt_rank`` + ``mamba_state`` + ``mamba_state``); ``delta
    # = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; the selective scan
    # (``ops/selective_scan.py``) gives ``Y``; ``out = (Y * silu(z))
    # W_out``.  ``mamba_state`` 0: no such layer; ``mamba_dt_rank`` 0:
    # ``ceil(hidden_size / 16)``
    mamba_state: int = 0
    mamba_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # a ``mamba2`` layer (Mamba-2's mixer, arXiv:2405.21060 as Nemotron-H's
    # model code has it): ``mamba2_heads`` heads of ``mamba2_head_dim``
    # (inner ``H P``), ``mamba2_groups`` groups that share a ``B`` and a
    # ``C`` of ``mamba2_state`` columns; ``[z | u | dt] = h W_in`` (inner,
    # inner + 2 G n, H); ``u = silu(conv(u) + b)``, causal and depthwise
    # over ``mamba_conv`` taps, over ``x``, ``B`` and ``C`` together; ``d =
    # softplus(dt + dt_bias)``; ``A = -exp(A_log)``, one number a head; the
    # scan in chunks of ``mamba2_chunk`` (``ops/ssd.py``) gives ``y``; ``out
    # = GroupRMSNorm(y * silu(z)) W_out``, the norm over each group's ``H P
    # / G`` channels under one learned scale.  ``mamba2_heads`` 0: no such
    # layer
    mamba2_heads: int = 0
    mamba2_head_dim: int = 64
    mamba2_groups: int = 1
    mamba2_state: int = 128
    mamba2_chunk: int = 128
    # a ``conv`` layer (LFM2's ``Lfm2ShortConv``): the mixer is a
    # double-gated short convolution and nothing else: ``[B | C | u] = h
    # W_in`` (three runs of ``hidden_size`` columns, in that order); ``v = B
    # * u``; ``c`` the causal depthwise convolution of ``v`` over
    # ``conv_taps`` taps a channel (``conv_L_cache``), zeros before the
    # start, no bias and NO activation; ``out = (C * c) W_out``.  No
    # positions, no softmax, no state beyond ``conv_taps - 1`` positions
    conv_taps: int = 3
    # layers that stand ONCE after the periods (entries as the pattern's),
    # counted in ``num_layers``; their parameters under ``suffix/<run>``
    layer_suffix: Tuple[str, ...] = ()
    # the dense feed-forward (``MLP``; a routed model's experts and shared
    # expert read the same two): 3 matrices, ``(act(h W_gate) * h W_up)
    # W_down``, or 2, ``act(h W_up) W_down``; ``mlp_activation`` ``"silu"``
    # or ``"relu2"`` (``relu(.)^2``, Nemotron-H's ``mlp_hidden_act``)
    mlp_matrices: int = 3
    mlp_activation: str = "silu"
    # a decoder-hybrid-decoder stack (SambaY, arXiv:2507.06607; YOCO,
    # arXiv:2405.05254): after the periods of ``layer_pattern`` the layers
    # of ``memory_layers`` stand ONCE (a ``mamba`` layer and a softmax layer,
    # parameters under ``memory/<run>``) and hand on the ``mamba`` layer's
    # scan output ``Y`` (before its gate) and the softmax layer's keys and
    # values; then ``cross_periods`` periods of ``cross_pattern`` (under
    # ``cross/<run>``, stacked ``[cross_periods, run, ...]``), whose kinds
    # read that memory: ``gmu`` (a gated memory unit, ``out = (Y * silu(h
    # W_in)) W_out``: no scan, no convolution) and ``xattn`` (a softmax layer
    # with a query and an output projection alone, over the handed keys and
    # values, causal).  All counted in ``num_layers``.
    # ``hybrid_layout`` derives all five from a depth and a period
    memory_layers: Tuple[str, ...] = ()
    cross_pattern: Tuple[str, ...] = ()
    cross_periods: int = 0
    # a looped stack (Ouro, arXiv:2510.25741; Universal Transformers): the
    # whole stack of ``num_layers`` run ``loop_steps`` times over the SAME
    # parameters, the final norm inside the loop: ``h_t = norm(layers(h_{t
    # - 1}))``, ``h_0`` the embeddings; the model returns the head's logits
    # of ``h_T``.  1: the stack once, the program as it was
    loop_steps: int = 1
    # each branch's OUTPUT normalised before it is added to the residual
    # stream (``x + norm(attn(norm(x)))``, and the feed-forward alike): two
    # more norms a layer, ``attn_out_norm`` and ``mlp_out_norm``
    sandwich_norm: bool = False
    # an exit gate read after every loop step, ``lam_t = sigmoid(h_t w_g +
    # b_g)`` a token, the head read after every loop step, and the model's
    # OWN objective sown into ``losses``: the expected cross entropy over
    # the exit distribution ``p_t = lam_t prod_{j<t} (1 - lam_j)`` (``p_T``:
    # the mass that is left) less ``exit_entropy_weight`` times its entropy
    # (Ouro's first training stage: a uniform prior over the exits).  The
    # model sees no labels: position ``i``'s target is ``input_ids[i + 1]``,
    # and the last position goes without one
    exit_gate: bool = False
    exit_entropy_weight: float = 0.0

    def __post_init__(self):
        valid = ("reference", "flash", "ring")
        if self.attention_impl not in valid:
            raise ValueError(
                f"attention_impl={self.attention_impl!r} not in {valid}"
            )
        if self.num_heads % self.num_kv_heads != 0:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm={self.qk_norm!r} not in "
                             "(False, True, 'head')")
        if self.index_topk and not (self.index_heads and self.index_head_dim):
            raise ValueError("index_topk needs index_heads and index_head_dim")
        if self.eva_window and (
                self.index_topk or not self.eva_chunk
                or self.eva_window % self.eva_chunk
                or self.num_kv_heads != self.num_heads):
            raise ValueError(
                "eva_window needs an eva_chunk that divides it, a key head "
                "a query head, and no indexer")
        entries = (self.layer_prefix + self.layer_pattern + self.layer_suffix
                   + self.memory_layers + self.cross_pattern)
        kinds = {layer_kind(entry)[0] for entry in entries}
        if entries and (
                not self.layer_pattern
                or kinds - set(LAYER_KINDS)
                or any(entry.partition(":")[2] not in ("", "dense", "alone")
                       or entry == "ffn:alone" for entry in entries)
                or self._body_layers() < 1
                or self._body_layers() % len(self.layer_pattern)
                or ("kda" in kinds and not self.kda_heads)
                or ("mla" in kinds and not self.mla_kv_rank)
                or ("swa" in kinds and self.sliding_window < 1)
                or ("mamba2" in kinds and (
                    self.mamba2_heads < 1
                    or self.mamba2_heads % self.mamba2_groups))
                or ("conv" in kinds and self.conv_taps < 1)
                or (self.layer_suffix and self.hybrid)
                or (any(layer_kind(entry)[1] for entry in entries)
                    and not self.dense_intermediate_size)):
            raise ValueError(
                f"layer_prefix={self.layer_prefix!r} layer_pattern="
                f"{self.layer_pattern!r} layer_suffix={self.layer_suffix!r}: "
                "entries '<kind>', '<kind>:dense', '<kind>:alone', 'ffn' or "
                f"'ffn:dense', kinds of {LAYER_KINDS}, a whole number of "
                f"periods in num_layers={self.num_layers} less the prefix "
                "and the suffix, kda_heads "
                "where there is a kda layer, mla_kv_rank where there is an "
                "mla layer, sliding_window where there is a swa layer, "
                "mamba2_heads a multiple of mamba2_groups where there is a "
                "mamba2 layer, conv_taps where there is a conv layer, no "
                "suffix on a hybrid stack and "
                "dense_intermediate_size where one is dense")
        if (self.mlp_matrices not in (2, 3)
                or self.mlp_activation not in ACTIVATIONS):
            raise ValueError(
                f"mlp_matrices={self.mlp_matrices!r} is 2 or 3 and "
                f"mlp_activation={self.mlp_activation!r} 'silu' or 'relu2'")
        reads = {"gmu", "xattn"}
        own = {layer_kind(e)[0] for e in
               self.layer_prefix + self.layer_pattern + self.memory_layers}
        if (self.norm not in ("rms", "layer")
                or (self.tie_embeddings and self.pred_heads > 1)
                or ("mamba" in kinds and not self.mamba_state)
                or own & reads
                or {layer_kind(e)[0] for e in self.cross_pattern} - reads
                or bool(self.cross_periods) != bool(self.cross_pattern)
                or (self.cross_pattern and sorted(
                    layer_kind(e)[0] in ("gqa", "swa")
                    for e in self.memory_layers) != [False, True])
                or (self.memory_layers and ("mamba" not in {
                    layer_kind(e)[0] for e in self.memory_layers}
                    or not self.diff_attention))
                or (self.diff_attention and (
                    self.num_heads % 2 or self.num_kv_heads % 2
                    or (self.num_heads // 2) % (self.num_kv_heads // 2)
                    or self.swa_heads or self.qk_norm or self.index_topk
                    or self.eva_window or self.block_diffusion
                    or self.attn_gate or self.attn_head_gate))):
            raise ValueError(
                "norm is 'rms' or 'layer'; tie_embeddings goes with one "
                "prediction head; a mamba layer needs mamba_state; gmu and "
                "xattn layers stand in cross_pattern alone, which needs "
                "cross_periods and memory_layers of one mamba and one "
                "softmax layer (under diff_attention: the plain softmax "
                "layer hands nothing on); diff_attention pairs an even number of query "
                "and of key heads and goes with no qk_norm, gate, indexer, "
                "eva_window, block_diffusion or swa_heads")
        if self.sliding_window and (
                self.index_topk or self.eva_window or self.block_diffusion
                or (self.swa_heads or self.num_heads) % self.num_kv_heads):
            raise ValueError(
                "sliding_window goes with no index_topk, eva_window or "
                "block_diffusion (each makes a mask of its own that knows no "
                "window), and swa_heads must be a multiple of num_kv_heads")
        rotary = self.head_dim * self.partial_rotary_factor
        if not 0 < self.partial_rotary_factor <= 1 or rotary % 2:
            raise ValueError(
                f"partial_rotary_factor={self.partial_rotary_factor!r} must "
                "leave an even number of a head's columns, at least two")
        if self.yarn_factor and (
                self.yarn_factor < 1 or self.yarn_original_max_len < 1):
            raise ValueError(
                "yarn_factor needs a factor of at least 1 and "
                "yarn_original_max_len")
        if self.block_diffusion and (
                self.index_topk or self.eva_window or self.layer_pattern
                or self.pred_heads > 1
                or not 0 <= self.mask_token_id < self.vocab_size):
            raise ValueError(
                "block_diffusion needs plain softmax layers, one prediction "
                "head and a mask_token_id inside the vocabulary")
        if self.loop_steps < 1 or (self.exit_gate and self.loop_steps < 2) or (
                self.loop_steps > 1 and (
                    self.layer_pattern or self.block_diffusion
                    or self.pred_heads > 1 or self.tie_embeddings
                    or not self.scan_layers
                    or self.feed_forward() is not MLP)):
            raise ValueError(
                "loop_steps > 1 loops the one scanned stack of equal dense "
                "layers (no layer_pattern, no block_diffusion, no routed "
                "feed-forward, one untied prediction head), and exit_gate "
                "needs a loop to leave")

    @property
    def own_objective(self) -> bool:
        """Whether the model sows its whole objective into ``losses``: a
        trainer then adds no next-token cross entropy on top."""
        return bool(self.block_diffusion or self.exit_gate)

    def step_rngs(self, step) -> dict:
        """The random streams ``model.apply`` wants in training step
        ``step`` (``rngs=``; empty: none): a function of the step alone, so
        the noise differs by step, costs the state nothing and a resumed
        job draws what the uninterrupted one drew.  A call without them (a
        forward check, ``model.init``) draws step 0's."""
        if not self.block_diffusion:
            return {}
        return {"noise": jax.random.fold_in(
            jax.random.PRNGKey(self.noise_seed), step)}

    def attention_numbers(self, kind: str) -> "AttentionNumbers":
        """What a softmax layer of ``kind`` (``gqa`` or ``swa``) runs at:
        the two kinds are one module and differ here alone."""
        if kind == "swa":
            return AttentionNumbers(
                self.swa_heads or self.num_heads, self.sliding_window,
                self.swa_rope_theta or self.rope_theta, self.head_dim, None)
        yarn = None
        if self.yarn_factor:
            yarn = (self.yarn_factor, self.yarn_original_max_len,
                    self.yarn_beta_fast, self.yarn_beta_slow,
                    self.yarn_attention_factor
                    or 0.1 * math.log(self.yarn_factor) + 1.0)
        return AttentionNumbers(
            self.num_heads, None, self.rope_theta,
            int(self.head_dim * self.partial_rotary_factor), yarn)

    def layer_runs(self, entries=None):
        """One period (or ``entries``: the prefix) as runs of equal layers,
        ``[(name, entry, length)]``: a run is one scan over its stacked
        parameters, under ``name`` (``<kind>_<run>``, ``<kind>_dense_<run>``
        for a dense entry)."""
        runs = []
        for entry in self.layer_pattern if entries is None else entries:
            if runs and runs[-1][1] == entry:
                runs[-1][2] += 1
            else:
                runs.append(
                    [f"{entry.replace(':', '_')}_{len(runs)}", entry, 1])
        return [tuple(run) for run in runs]

    def _body_layers(self) -> int:
        """The layers the periods of ``layer_pattern`` make up."""
        return (self.num_layers - len(self.layer_prefix)
                - len(self.layer_suffix) - len(self.memory_layers)
                - self.cross_periods * len(self.cross_pattern))

    @property
    def periods(self) -> int:
        return self._body_layers() // len(self.layer_pattern)

    @property
    def hybrid(self) -> bool:
        """Whether a layer is handed more than ``(x, positions, mask)``:
        the memory of a decoder-hybrid-decoder stack, and its own index in
        the stack (differential attention's ``lambda_init`` reads it)."""
        return bool(self.diff_attention or self.memory_layers)

    def layer_kinds(self):
        """The entry of every layer of a patterned stack, in order."""
        return (self.layer_prefix + self.layer_pattern * self.periods
                + self.layer_suffix
                + self.memory_layers + self.cross_pattern * self.cross_periods)

    def feed_forward(self):
        """The module class of the block after attention, built as
        ``cls(config, name="mlp")``: the model's choice (the dense SwiGLU
        here, the routed experts of ``models/moe.py`` there)."""
        return MLP

    def feed_forward_params(self) -> int:
        return self.mlp_matrices * self.hidden_size * self.intermediate_size

    @classmethod
    def llama2_7b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def llama2_1b(cls, **kw) -> "LlamaConfig":
        return cls(
            hidden_size=2048, intermediate_size=5504, num_layers=22,
            num_heads=16, num_kv_heads=16, head_dim=128, **kw,
        )

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test/debug size: runs on the 8-device CPU mesh in seconds."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_seq_len=128,
        )
        defaults.update(kw)
        return cls(**defaults)


#: the kinds a ``layer_pattern`` may name (``gmu`` and ``xattn``: a
#: ``cross_pattern`` alone; ``ffn``: a feed-forward with no mixer)
LAYER_KINDS = ("gqa", "kda", "mla", "swa", "mamba", "gmu", "xattn", "mamba2",
               "ffn", "conv")


def hybrid_layout(num_layers: int, mb_per_layer: int = 2) -> dict:
    """The fields of ``LlamaConfig`` that lay out a decoder-hybrid-decoder
    stack of ``num_layers`` by Phi-4-mini-flash's rule (``phi4flash``'s
    model code, ``L = num_layers``): layer ``i`` is a ``mamba`` layer where
    ``i % mb_per_layer == 0``, else attention, under the window where ``i <
    L/2`` and ``i`` is odd, else whole; the layers ``i >= L/2`` are the YOCO
    half: ``L/2`` (``mamba``) and ``L/2 + 1`` (whole attention) hand on their
    scan output and their keys and values, and from ``L/2 + 2`` an even
    ``i`` is a ``gmu`` layer and an odd one ``xattn``.  Returns
    ``layer_pattern``, ``memory_layers``, ``cross_pattern`` and
    ``cross_periods`` (the self-decoder's periods follow from
    ``num_layers``)."""
    half = num_layers // 2
    if num_layers % 4 or mb_per_layer < 1 or half % mb_per_layer:
        raise ValueError(
            f"num_layers={num_layers} must be a multiple of 4 and half of "
            f"it of mb_per_layer={mb_per_layer}")

    def kind(i):
        if i >= half + 2:
            return "xattn" if i % 2 else "gmu"
        if i % mb_per_layer == 0:
            return "mamba"
        return "swa" if i < half and i % 2 else "gqa"

    kinds = [kind(i) for i in range(num_layers)]
    pattern = tuple(kinds[:mb_per_layer])
    memory, cross = tuple(kinds[half: half + 2]), tuple(kinds[half + 2:])
    if (kinds[:half] != list(pattern) * (half // mb_per_layer)
            or "mamba" not in memory or "gqa" not in memory):
        raise ValueError(
            f"mb_per_layer={mb_per_layer} at {num_layers} layers gives "
            f"{kinds}: no whole periods before the pair that hands on")
    return dict(layer_pattern=pattern, memory_layers=memory,
                cross_pattern=cross[:2], cross_periods=len(cross) // 2)


class AttentionNumbers(NamedTuple):
    """``LlamaConfig.attention_numbers``: a softmax layer's own numbers."""

    heads: int
    #: positions a query sees, itself among them; ``None``: every earlier
    window: Optional[int]
    rope_theta: float
    #: a head's leading columns that RoPE turns
    rotary_dim: int
    #: ``None`` or (factor, original length, beta_fast, beta_slow,
    #: attention factor)
    yarn: Optional[Tuple[float, int, float, float, float]]


def layer_kind(entry: str):
    """``(kind, dense)`` of a pattern's entry ``"<kind>"``,
    ``"<kind>:dense"`` or ``"<kind>:alone"``."""
    kind, _, ffn = entry.partition(":")
    return kind, ffn == "dense"


def layer_branches(entry: str):
    """``(mixer, feed_forward)`` of a pattern's entry: the kind of module
    at ``attn`` or ``None`` (``"ffn"``: no mixer), and whether one stands at
    ``mlp`` (``"<kind>:alone"``: none)."""
    kind, _, ffn = entry.partition(":")
    return None if kind == "ffn" else kind, ffn != "alone"


def yarn_frequencies(dim: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float):
    """YaRN's ``dim // 2`` inverse frequencies, float64, with ``(low,
    high)``, the pairs its ramp runs between: over the pairs ``i`` of
    ``dim`` rotary columns, ``f_i = theta^(-2i/dim)``; a pair that turns
    ``n`` times in ``original`` positions lies at ``dim ln(original / (2 pi
    n)) / (2 ln theta)``; ``low`` is ``beta_fast`` turns' pair rounded
    down, ``high`` ``beta_slow`` turns' rounded up; ``ramp_i = clip((i -
    low) / (high - low), 0, 1)``; the frequency is ``f_i (1 - ramp_i) +
    (f_i / factor) ramp_i``: fast pairs as they were, slow pairs
    stretched."""
    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high if high != low else high + 0.001) - low), 0, 1)
    return freq * (1 - ramp) + freq / factor * ramp, (low, high)


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
          rotary_dim: Optional[int] = None, yarn=None,
          interleave: bool = False) -> jnp.ndarray:
    """Rotary position embedding; x: [B, S, H, D].  ``rotary_dim``: the
    head's leading columns that turn (halves convention within them), the
    rest pass as they are; ``yarn`` (``AttentionNumbers.yarn``): YaRN's
    frequencies, cos and sin both times its attention factor.
    ``interleave``: pair ``i`` is the neighbours ``(x[2i], x[2i+1])`` where
    it is ``(x[i], x[i + D/2])`` otherwise.  The result stands in the
    halves' layout either way (pair ``i``'s two results at ``i`` and ``i +
    D/2``): one strided read of the operand and no interleaving write, and
    a score is the same under any one permutation of both its operands'
    columns (Hugging Face's ``apply_rotary_pos_emb_interleave`` leaves the
    same layout)."""
    d = x.shape[-1]
    if rotary_dim is not None and rotary_dim < d:
        turned = _rope(x[..., :rotary_dim], positions, theta, None, yarn,
                       interleave)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    if yarn is None:
        freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        freq = jnp.asarray(
            yarn_frequencies(d, theta, *yarn[:4])[0], jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freq  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if yarn is not None:
        cos, sin = cos * yarn[4], sin * yarn[4]
    x32 = x.astype(jnp.float32)
    x1, x2 = ((x32[..., 0::2], x32[..., 1::2]) if interleave
              else jnp.split(x32, 2, axis=-1))
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)


def noise_blocks(ids, key, block, mask_id, eps=1e-3):
    """Block diffusion's forward process under the linear schedule:
    ``(noisy_ids, weights)`` of ``ids`` [B, S] in blocks of ``block``.  A
    block draws its rate ``t = eps + (1 - eps) u``, ``u ~ U(0, 1)``; a
    token is masked, ``m = 1``, with probability its block's ``t`` and
    then reads ``mask_id``; ``weights = m / t`` in float32, the NELBO's
    weight of the token's cross entropy."""
    B, S = ids.shape
    rate_key, token_key = jax.random.split(key)
    t = eps + (1.0 - eps) * jax.random.uniform(
        rate_key, (B, S // block), jnp.float32)
    t = jnp.repeat(t, block, axis=1)
    masked = jax.random.uniform(token_key, (B, S), jnp.float32) < t
    return (jnp.where(masked, jnp.asarray(mask_id, ids.dtype), ids),
            jnp.where(masked, 1.0 / t, 0.0))


class RMSNorm(nn.Module):
    eps: float
    dtype: Dtype
    param_dtype: Dtype
    axis_name: str = "embed"
    #: the parameter starts at 0 and scales by ``1 + scale``
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(
                nn.initializers.zeros if self.unit_offset
                else nn.initializers.ones, (self.axis_name,)),
            (x.shape[-1],),
            self.param_dtype,
        )
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        normed = x32 * jax.lax.rsqrt(var + self.eps)
        scale = scale.astype(jnp.float32)
        if self.unit_offset:
            scale = 1.0 + scale
        return (normed * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    """``(x - mean) / sqrt(var + eps) * scale + bias`` in float32 (the
    configuration's ``norm`` ``"layer"``)."""

    eps: float
    dtype: Dtype
    param_dtype: Dtype

    @nn.compact
    def __call__(self, x):
        def param(name, init):
            return self.param(
                name, nn.with_logical_partitioning(init, ("embed",)),
                (x.shape[-1],), self.param_dtype).astype(jnp.float32)

        scale = param("scale", nn.initializers.ones)
        bias = param("bias", nn.initializers.zeros)
        x32 = x.astype(jnp.float32)
        centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
        return (centred * jax.lax.rsqrt(var + self.eps) * scale
                + bias).astype(self.dtype)


def _norm_of(cfg):
    """The configuration's norm as ``norm(name=...)``."""
    if cfg.norm == "layer":
        return partial(LayerNorm, cfg.rms_norm_eps, cfg.dtype,
                       cfg.param_dtype)
    return partial(RMSNorm, cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                   unit_offset=cfg.norm_unit_offset)


def diff_lambda_init(depth):
    """Differential attention's ``lambda_init`` at layer ``depth`` of the
    stack (arXiv:2410.05258, equation 3)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


class Attention(nn.Module):
    config: LlamaConfig
    #: ``"gqa"`` or ``"swa"``: whose numbers of the configuration it runs
    #: at (``LlamaConfig.attention_numbers``); ``"xattn"``: ``gqa``'s, with
    #: no key or value projection: it reads the handed ``memory``'s
    kind: str = "gqa"

    @nn.compact
    def __call__(self, x, positions, mask, memory=None, depth=None,
                 keep=False):
        """``keep``: also return the keys and values, as projected (a
        memory layer of a decoder-hybrid-decoder stack hands them on)."""
        cfg = self.config
        own = cfg.attention_numbers(
            "gqa" if self.kind == "xattn" else self.kind)
        dense = partial(
            nn.DenseGeneral,
            use_bias=cfg.attention_bias,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        )
        if cfg.diff_attention:
            return self._differential(
                x, positions, mask, memory, depth, keep, own, dense)
        q = dense(
            features=(own.heads, cfg.head_dim),
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "heads", "head_dim")
            ),
            name="q_proj",
        )(x)
        k = dense(
            features=(cfg.num_kv_heads, cfg.head_dim),
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "kv_heads", "head_dim")
            ),
            name="k_proj",
        )(x)
        v = dense(
            features=(cfg.num_kv_heads, cfg.head_dim),
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "kv_heads", "head_dim")
            ),
            name="v_proj",
        )(x)
        if cfg.qk_norm == "head":
            q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                        "head_dim", name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                        "head_dim", name="k_norm")(k)
        elif cfg.qk_norm:
            def whole_width_norm(t, name):
                flat = t.reshape(*t.shape[:2], -1)
                norm = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                               name=name)
                return norm(flat).reshape(t.shape)

            q = whole_width_norm(q, "q_norm")
            k = whole_width_norm(k, "k_norm")
        q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
        k = nn.with_logical_constraint(k, ("batch", "seq", "kv_heads", "head_dim"))
        v = nn.with_logical_constraint(v, ("batch", "seq", "kv_heads", "head_dim"))

        if cfg.use_rope:
            q = _rope(q, positions, own.rope_theta, own.rotary_dim, own.yarn)
            k = _rope(k, positions, own.rope_theta, own.rotary_dim, own.yarn)

        # ``attn.core``: from q, k, v to the attention's output (the kind
        # table of ``observability/trace.py``); what is left under ``attn``
        # is projections.  The scope stands AROUND ``_attend``: the device
        # names a kernel after the innermost scope around it
        if cfg.index_topk:
            out = self._attend_indexed(x, q, k, v, positions, dense)
        elif cfg.eva_window:
            out = self._attend_eva(q, k, v)
        elif cfg.block_diffusion:
            from dlrover_tpu.ops.attention import block_diffusion_attention

            with jax.named_scope("attn.core"):
                out = block_diffusion_attention(q, k, v, cfg.block_diffusion)
        elif own.window is not None:
            # the sub-scope too stands around ``_attend``
            with jax.named_scope("attn.core"), jax.named_scope("window"):
                out = self._attend(q, k, v, mask, own.window)
        else:
            with jax.named_scope("attn.core"):
                out = self._attend(q, k, v, mask)
        if cfg.attn_head_gate:
            gate = dense(
                features=own.heads,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "heads")),
                name="head_gate_proj",
            )(x)
            out = out * nn.sigmoid(gate)[..., None]
        if cfg.attn_gate:
            gate = dense(
                features=(own.heads, cfg.head_dim),
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(),
                    ("embed", "heads", "head_dim")),
                name="gate_proj",
            )(x)
            out = out * nn.sigmoid(gate)
        out = nn.with_logical_constraint(
            out, ("batch", "seq", "heads", "head_dim")
        )
        return nn.DenseGeneral(
            features=x.shape[-1],
            axis=(-2, -1),
            use_bias=cfg.attention_bias,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("heads", "head_dim", "embed")
            ),
            name="o_proj",
        )(out)

    def _differential(self, x, positions, mask, memory, depth, keep, own,
                      dense):
        """The layer under ``diff_attention`` (the configuration's comment
        has the equations): q, k, v as ever (an ``xattn`` layer: q alone,
        over the memory's keys and values), the core in
        ``ops/attention.py::differential_attention``, the sub-norm, ``1 -
        lambda_init`` and the output projection over ``heads / 2`` heads of
        ``2 head_dim``."""
        from dlrover_tpu.ops.attention import differential_attention

        cfg, D = self.config, self.config.head_dim

        def heads_of(name, heads, axis):
            return dense(
                features=(heads, D), name=name,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(),
                    ("embed", axis, "head_dim")))(x)

        q = heads_of("q_proj", own.heads, "heads")
        if self.kind == "xattn":
            k, v = memory[1], memory[2]
        else:
            k = heads_of("k_proj", cfg.num_kv_heads, "kv_heads")
            v = heads_of("v_proj", cfg.num_kv_heads, "kv_heads")
        q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
        if cfg.use_rope:
            q = _rope(q, positions, own.rope_theta, own.rotary_dim, own.yarn)
            if self.kind != "xattn":    # the memory's keys are turned
                k = _rope(k, positions, own.rope_theta, own.rotary_dim,
                          own.yarn)
        handed = (k, v)

        def vector(name):
            return self.param(
                name, nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.1), ("head_dim",)),
                (D,), cfg.param_dtype).astype(jnp.float32)

        first = diff_lambda_init(depth)
        lam = (jnp.exp(jnp.sum(vector("lambda_q1") * vector("lambda_k1")))
               - jnp.exp(jnp.sum(vector("lambda_q2") * vector("lambda_k2")))
               + first)
        self.sow("stats", "diff_lambda", lam)
        # ``window`` (where there is one) around ``diff``: the innermost
        # sub-scope names the work, and both stand AROUND the core's call
        band = (contextlib.nullcontext() if own.window is None
                else jax.named_scope("window"))
        with jax.named_scope("attn.core"), band, jax.named_scope("diff"):
            out = differential_attention(
                q, k, v, lam, mask, own.window, cfg.attention_impl)
            out = RMSNorm(1e-5, jnp.float32, cfg.param_dtype, "head_dim",
                          name="sub_norm")(out)
            out = (out * (1.0 - first)).astype(cfg.dtype)
        out = nn.with_logical_constraint(
            out, ("batch", "seq", "heads", "head_dim"))
        out = nn.DenseGeneral(
            features=x.shape[-1], axis=(-2, -1),
            use_bias=cfg.attention_bias, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(),
                ("heads", "head_dim", "embed")),
            name="o_proj")(out)
        return (out, handed) if keep else out

    @staticmethod
    def differential_params(cfg, kind) -> int:
        heads = cfg.attention_numbers(
            "gqa" if kind == "xattn" else kind).heads
        bias = int(cfg.attention_bias)
        D, h = cfg.head_dim, cfg.hidden_size
        own_kv = 0 if kind == "xattn" else 2 * cfg.num_kv_heads * D * (
            h + bias)
        return (heads * D * (h + bias) + own_kv          # q, then k and v
                + heads * D * h + bias * h               # o
                + 4 * D + 2 * D)                         # lambdas, sub-norm

    def _attend_indexed(self, x, q, k, v, positions, dense):
        """Attention over the keys the indexer selects.  The indexer reads
        ``stop_gradient(x)`` and is taught by its own loss alone (sown into
        ``losses`` like a router's terms), never by the language model's."""
        from dlrover_tpu.ops.attention import indexed_sparse_attention

        cfg = self.config
        x = jax.lax.stop_gradient(x)

        def project(name, *features):
            return dense(
                features=features, name=name,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(),
                    ("embed",) + (None,) * len(features)),
            )(x)

        index_q = project("index_q_proj", cfg.index_heads, cfg.index_head_dim)
        index_k = nn.LayerNorm(
            epsilon=1e-6, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="index_k_norm")(project("index_k_proj", cfg.index_head_dim))
        index_w = project("index_w_proj", cfg.index_heads)
        index_q = _rope(index_q, positions, cfg.rope_theta)
        index_k = _rope(index_k[:, :, None], positions, cfg.rope_theta)[:, :, 0]
        with jax.named_scope("attn.core"):
            out, loss, low = indexed_sparse_attention(
                q, k, v, index_q, index_k, index_w, cfg.index_topk,
                cfg.index_block)
        self.sow("losses", "index", loss / cfg.num_layers)
        self.sow("stats", "index_loss", loss)
        self.sow("stats", "index_low_margin_share", low)
        return out

    def _attend_eva(self, q, k, v):
        """Attention over the window's own keys and the learned summaries
        of every earlier window's chunks; the two vectors a head that pool
        a chunk's keys and values are this module's parameters."""
        from dlrover_tpu.ops.attention import eva_attention

        cfg = self.config

        def pooling_vector(name):
            # normal, clipped to [-1, 1], times head_dim ** -0.5
            return self.param(
                name,
                nn.with_logical_partitioning(
                    lambda key, shape, dtype: cfg.head_dim ** -0.5 * jnp.clip(
                        jax.random.normal(key, shape, dtype), -1.0, 1.0),
                    ("heads", "head_dim")),
                (cfg.num_heads, cfg.head_dim), cfg.param_dtype,
            )

        mu, phi = pooling_vector("adaptive_mu_k"), pooling_vector("adaptive_phi")
        with jax.named_scope("attn.core"):
            out, summary_mass, pool_weight = eva_attention(
                q, k, v, mu, phi, cfg.eva_window, cfg.eva_chunk)
        self.sow("stats", "eva_summary_mass_share", summary_mass)
        self.sow("stats", "eva_pool_weight_max", pool_weight)
        return out

    def _attend(self, q, k, v, mask, window=None):
        cfg = self.config
        if cfg.attention_impl == "flash":
            from dlrover_tpu.ops.attention import flash_attention

            # what the layer does around the kernel, on the kernel's line
            around = {**({} if cfg.use_rope else {"rope": "none"}),
                      **({"gate": "sigmoid"} if cfg.attn_gate else {}),
                      **({"gate": "sigmoid_a_head"}
                         if cfg.attn_head_gate else {})}
            return flash_attention(q, k, v, causal=True, path_attrs=around,
                                   window=window)
        if cfg.attention_impl == "ring":
            if window is not None:
                raise NotImplementedError(
                    "attention_impl='ring' knows no window: a swa layer "
                    "runs under 'flash' or 'reference'")
            # NOTE: the ring path is causal-only; the surrounding model
            # always builds a causal mask, and any future padding mask
            # must extend ring_attention before being honored here.
            from dlrover_tpu.ops.attention import reference_attention
            from dlrover_tpu.ops.ring_attention import (
                active_mesh,
                ring_attention_sharded,
            )

            mesh = active_mesh()
            if mesh is not None and mesh.shape.get("cp", 1) > 1:
                return ring_attention_sharded(mesh, q, k, v, causal=True)
            import warnings

            warnings.warn(
                "attention_impl='ring' without an active cp>1 mesh context "
                "— falling back to reference attention (full S x S scores, "
                "KV all-gather). Wrap calls in `with mesh:` with a cp axis.",
                stacklevel=2,
            )
            return reference_attention(q, k, v, mask)
        from dlrover_tpu.ops.attention import reference_attention

        return reference_attention(q, k, v, mask, window)


def _kda_decay_init(low, high):
    """``log`` of a value uniform in ``[low, high]`` (fla's ``A_log``: the
    decay's rate a head, 1 to 16)."""
    def init(key, shape, dtype):
        return jnp.log(jax.random.uniform(key, shape, dtype, low, high))
    return init


def _kda_dt_bias_init(dt_min=1e-3, dt_max=0.1, floor=1e-4):
    """The inverse softplus of a step size log-uniform in ``[dt_min,
    dt_max]`` (fla's ``dt_bias``, Mamba's): ``softplus(bias)`` is the step."""
    def init(key, shape, dtype):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (
            math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


class DeltaAttention(nn.Module):
    """Kimi Delta Attention (arXiv:2510.26692; flash-linear-attention's
    ``fla/layers/kda.py``): a gated delta rule with a decay for every
    channel, in place of softmax attention in a ``kda`` layer.  q, k and v
    go through a causal depthwise convolution of ``kda_conv`` taps and
    SiLU; q and k are L2-normalised (q times ``d^-1/2``); the log decay is
    ``g = -exp(A_log) softplus((h W_f1) W_f2 + dt_bias)`` a channel and
    ``beta = 2 sigmoid(h w_beta)`` (the 2: negative eigenvalues allowed);
    the state's read-out is RMS-normalised a head and gated by
    ``sigmoid((h W_g1) W_g2)`` before the output projection.  By the
    configuration (Ling-3.0's): ``kda_full_rank_gates``, one full-rank
    projection each for the decay and the gate; ``kda_decay_lower_bound``,
    the decay ``bound * sigmoid(exp(A_log) (f + dt_bias))``;
    ``kda_neg_eigval`` false, beta without the 2.  The
    sub-scopes under ``attn.core`` are the kind table's
    (``observability/trace.py``): ``conv``, ``decay``, ``chunk``, ``state``
    (both inside ``ops/linear_attention.py::kda``), ``gate``."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, mask):
        from dlrover_tpu.ops.linear_attention import kda, kda_core

        cfg = self.config
        H, D, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
        dense = partial(nn.DenseGeneral, use_bias=False,
                        param_dtype=cfg.param_dtype)

        def heads_of(name):
            return dense(
                features=(H, D), dtype=cfg.dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(),
                    ("embed", "heads", "head_dim")),
                name=name)

        def low_rank(name, dtype=cfg.dtype):
            """``(x W_1) W_2``: hidden -> ``D`` -> heads x ``D``."""
            down = dense(
                features=D, dtype=cfg.dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", None)),
                name=name + "_down")(x)
            return dense(
                features=(H, D), dtype=dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(),
                    (None, "heads", "head_dim")),
                name=name + "_up")(down)

        def short_conv(name, t):
            """SiLU of the causal depthwise convolution: tap ``i`` weighs
            position ``t - (taps - 1) + i``, zeros before the start."""
            weight = self.param(
                name,
                nn.with_logical_partitioning(
                    # a depthwise Conv1d's default: uniform in +-1/sqrt(taps)
                    lambda key, shape, dtype: jax.random.uniform(
                        key, shape, dtype, -1.0, 1.0) * taps ** -0.5,
                    (None, "heads", "head_dim")),
                (taps, H, D), cfg.param_dtype).astype(jnp.float32)
            padded = jnp.pad(t, ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
            S = t.shape[1]
            return nn.silu(sum(
                padded[:, i: i + S].astype(jnp.float32) * weight[i]
                for i in range(taps)))

        def unit(t):
            return t * jax.lax.rsqrt(
                jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

        q, k, v = heads_of("q_proj")(x), heads_of("k_proj")(x), heads_of(
            "v_proj")(x)
        # the decay and beta steer exponentials: their last projections
        # give float32 (operands still multiplied in the compute dtype)
        def gate_input(name, dtype=cfg.dtype):
            if not cfg.kda_full_rank_gates:
                return low_rank(name, dtype)
            return dense(
                features=(H, D), dtype=dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(),
                    ("embed", "heads", "head_dim")),
                name=name + "_proj")(x)

        decay_in = gate_input("f", jnp.float32)
        beta_in = dense(
            features=H, dtype=jnp.float32,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "heads")),
            name="beta_proj")(x)
        rate = self.param(
            "A_log", nn.with_logical_partitioning(
                _kda_decay_init(1.0, 16.0), ("heads",)),
            (H,), cfg.param_dtype)
        dt_bias = self.param(
            "dt_bias", nn.with_logical_partitioning(
                _kda_dt_bias_init(), ("heads", "head_dim")),
            (H, D), cfg.param_dtype)
        gate_in = gate_input("g")
        with jax.named_scope("attn.core"):
            with jax.named_scope("conv"):
                q, k, v = (short_conv(name, t) for name, t in (
                    ("q_conv", q), ("k_conv", k), ("v_conv", v)))
            with jax.named_scope("decay"):
                q = (unit(q) * D ** -0.5).astype(cfg.dtype)
                k = unit(k).astype(cfg.dtype)
                v = v.astype(cfg.dtype)
                if cfg.kda_decay_lower_bound:
                    g = cfg.kda_decay_lower_bound * nn.sigmoid(
                        jnp.exp(rate.astype(jnp.float32))[:, None]
                        * (decay_in + dt_bias.astype(jnp.float32)))
                else:
                    g = -jnp.exp(rate.astype(jnp.float32))[:, None] * (
                        jax.nn.softplus(
                            decay_in + dt_bias.astype(jnp.float32)))
                beta = nn.sigmoid(beta_in)
                if cfg.kda_neg_eigval:
                    beta = 2.0 * beta
                self.sow("stats", "kda_beta_over_one_share",
                         jnp.mean(beta > 1.0))
                # how far the state remembers: the median channel's half
                # life in tokens at its mean decay
                self.sow("stats", "kda_decay_half_life", jnp.median(
                    math.log(2.0) / -jnp.mean(g, axis=(0, 1))))
            q = nn.with_logical_constraint(
                q, ("batch", "seq", "heads", "head_dim"))
            trace.note_trace_time(
                "attention.path", impl="kda", seq=x.shape[1], heads=H,
                head_dim=D, chunk=min(cfg.kda_chunk, x.shape[1]), conv=taps,
                state_dtype="float32",
                **kda_core(x.shape[1], cfg.kda_chunk, D))
            out = kda(q, k, v, g, beta, cfg.kda_chunk)
            with jax.named_scope("gate"):
                out = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                              "head_dim", name="o_norm")(out)
                out = out * nn.sigmoid(gate_in)
        out = nn.with_logical_constraint(
            out, ("batch", "seq", "heads", "head_dim"))
        return nn.DenseGeneral(
            features=x.shape[-1], axis=(-2, -1), use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(),
                ("heads", "head_dim", "embed")),
            name="o_proj")(out)

    @staticmethod
    def num_params(cfg) -> int:
        H, D = cfg.kda_heads, cfg.kda_head_dim
        gates = (2 * cfg.hidden_size * H * D if cfg.kda_full_rank_gates
                 else 2 * (cfg.hidden_size * D + D * H * D))
        return (4 * cfg.hidden_size * H * D          # q, k, v, o
                + gates                              # f and g
                + cfg.hidden_size * H                # beta
                + 3 * cfg.kda_conv * H * D           # the three convolutions
                + H + H * D + D)                     # A_log, dt_bias, o_norm


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2's MLA, arXiv:2405.04434,
    without a query bottleneck; Ling-3.0's softmax layers): ``q = h W_q``,
    a head ``[q_nope | q_pe]``; ``[c | k_pe] = h W_kva``, the latent ``c``
    RMS-normalised; ``[k_nope | v] = c W_kvb`` a head; RoPE on ``q_pe`` a
    head and on the ONE ``k_pe``, which every head shares (by halves, or
    with ``mla_rope_interleave`` by neighbouring pairs: ``_rope``); ``o =
    softmax_causal((q_nope k_nope^T + q_pe k_pe^T) / sqrt(nope + rope)) v``
    (``ops/attention.py::latent_attention``: scores in two products, never
    a key of ``nope + rope`` a head in HBM); with ``mla_head_gate`` ``o_h *
    sigmoid(h w_gate)_h``, one gate a head; the output projection.  Scope
    ``attn`` / ``latent`` holds the latent's projections, norm and the
    rotary part (kind ``attn.proj``), ``attn.core`` / ``latent`` the
    core."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, mask):
        from dlrover_tpu.ops.attention import latent_attention

        cfg = self.config
        H, nope, rope, wide = (cfg.num_heads, cfg.mla_nope_dim,
                               cfg.mla_rope_dim, cfg.mla_v_dim)
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)

        def init(*axes):
            return nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), axes)

        q = dense(features=(H, nope + rope), name="q_proj",
                  kernel_init=init("embed", "heads", "head_dim"))(x)
        with jax.named_scope("latent"):
            # on every chip of a layer: the down-projection and its norm
            down = dense(features=cfg.mla_kv_rank + rope, name="kv_a_proj",
                         kernel_init=init("embed", None))(x)
            latent = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                             None, name="kv_a_norm")(
                                 down[..., :cfg.mla_kv_rank])
            up = dense(features=(H, nope + wide), name="kv_b_proj",
                       kernel_init=init(None, "heads", "head_dim"))(latent)
            pairs = cfg.mla_rope_interleave
            k_pe = _rope(down[..., None, cfg.mla_kv_rank:], positions,
                         cfg.rope_theta, interleave=pairs)[:, :, 0]
            q_pe = _rope(q[..., nope:], positions, cfg.rope_theta,
                         interleave=pairs)
        q_nope = nn.with_logical_constraint(
            q[..., :nope], ("batch", "seq", "heads", "head_dim"))
        with jax.named_scope("attn.core"):
            out = latent_attention(
                q_nope, q_pe, up[..., :nope], k_pe, up[..., nope:],
                rope="pairs" if pairs else "halves")
        if cfg.mla_head_gate:
            gate = dense(features=H, name="gate_proj",
                         kernel_init=init("embed", "heads"))(x)
            out = out * nn.sigmoid(gate)[..., None]
        out = nn.with_logical_constraint(
            out, ("batch", "seq", "heads", "head_dim"))
        return nn.DenseGeneral(
            features=x.shape[-1], axis=(-2, -1), use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=init("heads", "head_dim", "embed"),
            name="o_proj")(out)

    @staticmethod
    def num_params(cfg) -> int:
        H, nope, rope, wide = (cfg.num_heads, cfg.mla_nope_dim,
                               cfg.mla_rope_dim, cfg.mla_v_dim)
        return (cfg.hidden_size * H * (nope + rope)            # q
                + cfg.hidden_size * (cfg.mla_kv_rank + rope)   # down
                + cfg.mla_kv_rank                              # its norm
                + cfg.mla_kv_rank * H * (nope + wide)          # up
                + (cfg.hidden_size * H if cfg.mla_head_gate else 0)
                + H * wide * cfg.hidden_size)                  # o


def _ssm_inner(cfg) -> int:
    return cfg.mamba_expand * cfg.hidden_size


def _ssm_dt_rank(cfg) -> int:
    return cfg.mamba_dt_rank or -(-cfg.hidden_size // 16)


def _projection(cfg, features, name, axes, **kw):
    """A bias-free projection in the compute dtype, its kernel
    lecun-normal under the logical ``axes``."""
    return nn.DenseGeneral(
        features=features, name=name,
        **{"use_bias": False, "dtype": cfg.dtype,
           "param_dtype": cfg.param_dtype,
           "kernel_init": nn.with_logical_partitioning(
               nn.initializers.lecun_normal(), axes), **kw})


def _tap_init(taps):
    """A depthwise Conv1d's default, weight and bias alike: uniform in
    ``+-taps^-1/2``."""
    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -1.0, 1.0) * (
            taps ** -0.5)
    return init


def _tap_conv(t, weight, bias):
    """SiLU of the causal depthwise convolution of ``t`` ``[B, S,
    channels]`` in float32: ``weight`` ``[taps, channels]``, tap ``i``
    weighs position ``t - (taps - 1) + i``, zeros before the start."""
    from dlrover_tpu.ops.short_conv import taps_sum

    return nn.silu(bias + taps_sum(t, weight))


class MambaMixer(nn.Module):
    """Mamba-1's mixer (arXiv:2312.00752; the configuration's comment has
    the equations), in place of attention in a ``mamba`` layer.  The state,
    ``delta``, ``A`` and the scan's arithmetic are float32; ``W_x`` and
    ``W_dt`` give float32 (operands multiplied as the backend multiplies
    float32: bfloat16 with float32 accumulation on a TPU); the other
    projections run in the compute dtype.  Sub-scopes under ``attn.core``:
    ``conv``, ``decay`` (``delta``, ``A`` and the counter), ``scan``
    (``ops/selective_scan.py``), ``gate``."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, mask, memory=None, depth=None,
                 keep=False):
        """``keep``: also return ``Y``, the scan's output before the gate
        (float32)."""
        from dlrover_tpu.ops.selective_scan import scan_core, selective_scan

        cfg = self.config
        inner, N, taps = _ssm_inner(cfg), cfg.mamba_state, cfg.mamba_conv
        rank = _ssm_dt_rank(cfg)
        S = x.shape[1]

        def channel_param(name, init, *lead):
            return self.param(
                name, nn.with_logical_partitioning(
                    init, (None,) * len(lead) + ("mlp",)),
                lead + (inner,), cfg.param_dtype).astype(jnp.float32)

        both = _projection(cfg, 2 * inner, "in_proj", ("embed", "mlp"))(x)
        a, z = both[..., :inner], both[..., inner:]
        weight = channel_param("conv_weight", _tap_init(taps), taps)
        bias = channel_param("conv_bias", _tap_init(taps))
        with jax.named_scope("attn.core"), jax.named_scope("conv"):
            a = _tap_conv(a, weight, bias).astype(cfg.dtype)
        a = nn.with_logical_constraint(a, ("batch", "seq", "mlp"))
        steer = _projection(cfg, rank + 2 * N, "x_proj", ("mlp", None),
                            dtype=jnp.float32)(a)
        step_in = _projection(
            cfg, inner, "dt_proj", (None, "mlp"), dtype=jnp.float32,
            use_bias=True, bias_init=nn.with_logical_partitioning(
                _kda_dt_bias_init(), ("mlp",)),
            # Mamba's ``dt_init`` "random": uniform in +-rank^-1/2
            kernel_init=nn.with_logical_partitioning(
                lambda key, shape, dtype: jax.random.uniform(
                    key, shape, dtype, -1.0, 1.0) * rank ** -0.5,
                (None, "mlp")))(steer[..., :rank])
        rate = self.param(
            "A_log", nn.with_logical_partitioning(
                lambda key, shape, dtype: jnp.broadcast_to(jnp.log(
                    jnp.arange(1, shape[1] + 1, dtype=dtype)), shape),
                ("mlp", None)),
            (inner, N), cfg.param_dtype)
        skip = channel_param("D", nn.initializers.ones)
        with jax.named_scope("attn.core"):
            with jax.named_scope("decay"):
                delta = jax.nn.softplus(step_in)
                A = -jnp.exp(rate.astype(jnp.float32))
                # how far the state hears: the median of ``exp(delta A)``
                # over 16 positions of the sequence and every eighth channel
                # (all of its columns): a sort of a hundred thousand numbers,
                # where every channel's took 5 ms a layer on a v5e
                sample = jax.lax.stop_gradient(
                    delta[:, :: max(S // 16, 1), ::8][..., None] * A[::8])
                self.sow("stats", "ssm_decay_p50",
                         jnp.median(jnp.exp(sample)))
            trace.note_trace_time(
                "attention.path", impl="mamba", seq=S, channels=inner,
                state=N, conv=taps, dt_rank=rank, state_dtype="float32",
                **scan_core(S, inner, N))
            y = selective_scan(a, delta, A, steer[..., rank: rank + N],
                               steer[..., rank + N:], skip, cfg.dtype)
            with jax.named_scope("gate"):
                out = (y * nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)
        out = nn.with_logical_constraint(out, ("batch", "seq", "mlp"))
        out = _projection(cfg, x.shape[-1], "out_proj", ("mlp", "embed"))(out)
        return (out, y) if keep else out

    @staticmethod
    def num_params(cfg) -> int:
        inner, N, rank = _ssm_inner(cfg), cfg.mamba_state, _ssm_dt_rank(cfg)
        return (cfg.hidden_size * 2 * inner             # in
                + cfg.mamba_conv * inner + inner        # the convolution
                + inner * (rank + 2 * N)                # x
                + rank * inner + inner                  # dt
                + inner * N + inner                     # A_log, D
                + inner * cfg.hidden_size)              # out


class Mamba2Mixer(nn.Module):
    """Mamba-2's mixer (arXiv:2405.21060; the configuration's comment has
    the equations), in place of attention in a ``mamba2`` layer.  ONE input
    projection to ``[z | x B C | dt]``; the convolution (``_tap_conv``,
    ``MambaMixer``'s) over ``x``, ``B`` and ``C`` together; ``B`` and ``C``
    a GROUP of heads, not a head; the scan's decay one number a head
    (``ops/ssd.py``); the gate BEFORE the norm, which is RMS over each
    group's channels.  ``dt``, ``A``, the decay's running sums, the state
    between chunks and the norm are float32; the projections and the
    scan's products run in the compute dtype with float32 accumulation.
    Sub-scopes under ``attn.core``: ``conv``, ``decay`` (``d``, ``A`` and
    the counter), ``ssd`` (``ops/ssd.py``), ``gate``."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, mask):
        from dlrover_tpu.ops.ssd import ssd, ssd_core

        cfg = self.config
        H, P, G, N = (cfg.mamba2_heads, cfg.mamba2_head_dim,
                      cfg.mamba2_groups, cfg.mamba2_state)
        inner, taps = H * P, cfg.mamba_conv
        wide = inner + 2 * G * N        # what the convolution runs over
        B, S = x.shape[:2]

        def param(name, init, shape, axes):
            return self.param(
                name, nn.with_logical_partitioning(init, axes), shape,
                cfg.param_dtype).astype(jnp.float32)

        all_three = _projection(cfg, inner + wide + H, "in_proj",
                                ("embed", None))(x)
        z, u, dt = (all_three[..., :inner], all_three[..., inner: inner + wide],
                    all_three[..., inner + wide:])
        weight = param("conv_weight", _tap_init(taps), (taps, wide),
                       (None, "mlp"))
        bias = param("conv_bias", _tap_init(taps), (wide,), ("mlp",))
        rate = param("A_log", _kda_decay_init(1.0, 16.0), (H,), ("heads",))
        dt_bias = param("dt_bias", _kda_dt_bias_init(), (H,), ("heads",))
        skip = param("D", nn.initializers.ones, (H,), ("heads",))
        with jax.named_scope("attn.core"):
            with jax.named_scope("conv"):
                u = _tap_conv(u, weight, bias).astype(cfg.dtype)
            with jax.named_scope("decay"):
                step = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
                A = -jnp.exp(rate)
                # whether the state carries: the median of ``exp(d A)`` over
                # the heads at 1024 positions of the sequence (a sort of
                # some ten thousand numbers)
                sample = jax.lax.stop_gradient(
                    step[:, :: max(S // 1024, 1)] * A)
                self.sow("stats", "ssd_decay_p50",
                         jnp.median(jnp.exp(sample)))
            heads = nn.with_logical_constraint(
                u[..., :inner].reshape(B, S, H, P),
                ("batch", "seq", "heads", "head_dim"))
            trace.note_trace_time(
                "attention.path", impl="mamba2", seq=S, heads=H, head_dim=P,
                groups=G, state=N, conv=taps, state_dtype="float32",
                **ssd_core(S, cfg.mamba2_chunk))
            y = ssd(heads, step, A,
                    u[..., inner: inner + G * N].reshape(B, S, G, N),
                    u[..., inner + G * N:].reshape(B, S, G, N), skip,
                    cfg.mamba2_chunk)
            with jax.named_scope("gate"):
                # gate first, then RMS over each group's channels
                gated = (y.reshape(B, S, inner)
                         * nn.silu(z.astype(jnp.float32))).reshape(
                             B, S, G, inner // G)
                scale = param("norm_scale", nn.initializers.ones, (inner,),
                              ("mlp",))
                out = (gated * jax.lax.rsqrt(
                    jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
                    + cfg.rms_norm_eps)).reshape(B, S, inner) * scale
                out = out.astype(cfg.dtype)
        out = nn.with_logical_constraint(out, ("batch", "seq", "mlp"))
        return _projection(cfg, x.shape[-1], "out_proj", ("mlp", "embed"))(out)

    @staticmethod
    def num_params(cfg) -> int:
        H, G, N = cfg.mamba2_heads, cfg.mamba2_groups, cfg.mamba2_state
        inner = H * cfg.mamba2_head_dim
        wide = inner + 2 * G * N
        return (cfg.hidden_size * (inner + wide + H)    # in
                + cfg.mamba_conv * wide + wide          # the convolution
                + 3 * H + inner                         # A_log, dt_bias, D; norm
                + inner * cfg.hidden_size)              # out


class ShortConvMixer(nn.Module):
    """A double-gated short convolution (LFM2's ``Lfm2ShortConv``; the
    configuration's comment has the equations), in place of attention in a
    ``conv`` layer.  The two projections run in the compute dtype and stand
    under the layer's ``attn`` scope; the gates and the taps, float32
    arithmetic on their operands (``ops/short_conv.py``, with the backward
    pass's own rule), under ``attn.core`` / ``gconv``.  The taps are
    ``_tap_init``'s and go through the scans' shifting routine, WITHOUT its
    bias and SiLU."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, mask):
        from dlrover_tpu.ops.short_conv import (
            gated_short_conv, past_tap_share)

        cfg = self.config
        wide, taps = x.shape[-1], cfg.conv_taps
        all_three = _projection(cfg, 3 * wide, "in_proj",
                                ("embed", "mlp"))(x)
        weight = self.param(
            "conv_weight", nn.with_logical_partitioning(
                _tap_init(taps), (None, "mlp")),
            (taps, wide), cfg.param_dtype).astype(jnp.float32)
        trace.note_trace_time(
            "attention.path", impl="short_conv", seq=x.shape[1],
            channels=wide, conv=taps, core="jnp")
        with jax.named_scope("attn.core"), jax.named_scope("gconv"):
            # an operation of its own between two barriers, as a ``gmu``
            # layer's gate: left to the compiler the second gate may be
            # fused into the output projection's operand and take its name,
            # and ``gconv_ms_per_step`` reads part of the core
            b, c, u = jax.lax.optimization_barrier((
                all_three[..., :wide], all_three[..., wide: 2 * wide],
                all_three[..., 2 * wide:]))
            # whether a position hears the ones before it: the share of
            # the convolution's result that the earlier taps make
            self.sow("stats", "gconv_past_tap_share",
                     past_tap_share(b, u, weight))
            out = jax.lax.optimization_barrier(
                gated_short_conv(b, c, u, weight))
        out = nn.with_logical_constraint(out, ("batch", "seq", "mlp"))
        return _projection(cfg, wide, "out_proj", ("mlp", "embed"))(out)

    @staticmethod
    def num_params(cfg) -> int:
        return (cfg.hidden_size * 3 * cfg.hidden_size       # in
                + cfg.conv_taps * cfg.hidden_size           # the taps
                + cfg.hidden_size * cfg.hidden_size)        # out


class GatedMemoryUnit(nn.Module):
    """A gated memory unit (SambaY, arXiv:2507.06607), in place of attention
    in a ``gmu`` layer: ``out = (Y * silu(h W_in)) W_out`` with ``Y`` the
    scan output the stack's ``mamba`` memory layer handed on: no scan, no
    convolution.  Sub-scope ``gmu`` under ``attn.core``."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, mask, memory=None, depth=None,
                 keep=False):
        cfg = self.config
        inner = _ssm_inner(cfg)
        gate = _projection(cfg, inner, "in_proj", ("embed", "mlp"))(x)
        with jax.named_scope("attn.core"), jax.named_scope("gmu"):
            # an operation of its own between two barriers: left to the
            # compiler the product is fused into the input projection's
            # epilogue, or into the output projection's operand, and takes
            # the projection's name; here ``gmu_ms_per_step`` can read it
            # (forward and rematerialised pass; its gradient is fused into
            # what is ``handed``).  The price is the gate written and read
            # once more a pass, 0.17 GB at 16,384 positions
            gate = jax.lax.optimization_barrier(gate)
            out = jax.lax.optimization_barrier(
                (memory[0].astype(jnp.float32)
                 * nn.silu(gate.astype(jnp.float32))).astype(cfg.dtype))
        out = nn.with_logical_constraint(out, ("batch", "seq", "mlp"))
        return _projection(cfg, x.shape[-1], "out_proj", ("mlp", "embed"))(out)

    @staticmethod
    def num_params(cfg) -> int:
        return 2 * cfg.hidden_size * _ssm_inner(cfg)


def _chip_rows(x):
    """``(the rows of ``x`` ``[B, S, E]`` a chip holds after the mesh's
    sharding of ``batch`` and ``seq``, a device of this process)``: the
    active mesh's, and without one the whole of ``x`` on the default
    device."""
    from dlrover_tpu.ops.ring_attention import active_mesh
    from dlrover_tpu.parallel.sharding import ways_split

    rows = x.shape[0] * x.shape[1]
    mesh = active_mesh()
    if mesh is None:
        return rows, jax.local_devices()[0]
    # inside a ``shard_map`` over the mesh ``x`` is a chip's share already
    if not jax.sharding.get_abstract_mesh().manual_axes:
        rows //= ways_split(mesh, ("batch", "seq"),
                            list(nn.get_logical_axis_rules()) or None)
    return rows, mesh.local_devices[0]


class MLP(nn.Module):
    config: LlamaConfig
    #: a decoder layer's own feed-forward, whose gate and up products the
    #: layer's rematerialisation keeps where they fit (``kept.py``'s
    #: ``MLP_PRODUCTS``); not a shared expert beside routed ones, whose
    #: layer keeps the routed products
    keeps_products: bool = True

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = partial(
            nn.DenseGeneral,
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        )
        if cfg.mlp_matrices == 2:   # ``act(x W_up) W_down``: no gate
            h = ACTIVATIONS[cfg.mlp_activation](_projection(
                cfg, cfg.intermediate_size, "up_proj", ("embed", "mlp"))(x))
            h = nn.with_logical_constraint(h, ("batch", "seq", "mlp"))
            return _projection(
                cfg, x.shape[-1], "down_proj", ("mlp", "embed"))(h)
        gate = dense(
            features=cfg.intermediate_size,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "mlp")
            ),
            name="gate_proj",
        )(x)
        up = dense(
            features=cfg.intermediate_size,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "mlp")
            ),
            name="up_proj",
        )(x)
        if self.keeps_products and cfg.remat:
            rows, device = _chip_rows(x)
            if kept.keeps_mlp_products(
                    cfg.num_layers * cfg.loop_steps, rows,
                    cfg.intermediate_size, cfg.dtype,
                    kept.device_bytes(device)):
                gate, up = kept.named(kept.MLP_PRODUCTS, gate, up)
                kept.note("mlp", **{kept.MLP_PRODUCTS: kept.mlp_products_bytes(
                    rows, cfg.intermediate_size, cfg.dtype)})
        h = ACTIVATIONS[cfg.mlp_activation](gate) * up
        h = nn.with_logical_constraint(h, ("batch", "seq", "mlp"))
        return dense(
            features=x.shape[-1],
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("mlp", "embed")
            ),
            name="down_proj",
        )(h)


#: the module at ``attn`` by the layer's kind
ATTENTION_OF = {"gqa": Attention, "kda": DeltaAttention,
                "mla": LatentAttention, "swa": partial(Attention, kind="swa"),
                "mamba": MambaMixer, "gmu": GatedMemoryUnit,
                "xattn": partial(Attention, kind="xattn"),
                "mamba2": Mamba2Mixer, "conv": ShortConvMixer}
#: the kinds whose module takes ``memory``, ``depth`` and ``keep``
HYBRID_KINDS = ("gqa", "swa", "mamba", "gmu", "xattn")


class DecoderLayer(nn.Module):
    config: LlamaConfig
    #: a pattern's entry: of ``LAYER_KINDS``, which module stands at
    #: ``attn``; with ``:dense``, the dense feed-forward at ``mlp``; with
    #: ``:alone`` no ``mlp``, and ``ffn`` no ``attn`` (``layer_branches``)
    kind: str = "gqa"
    #: a memory layer of a decoder-hybrid-decoder stack: the call returns
    #: ``(x, what its mixer hands on)``
    keeps: bool = False

    @nn.compact
    def __call__(self, x, positions, mask, memory=None, depth=None):
        cfg = self.config
        _, dense = layer_kind(self.kind)
        mixer, has_ffn = layer_branches(self.kind)
        feed_forward, ffn_cfg = cfg.feed_forward(), cfg
        if dense:
            feed_forward, ffn_cfg = MLP, dataclasses.replace(
                cfg, intermediate_size=cfg.dense_intermediate_size)
        norm = _norm_of(cfg)
        # ``x`` is the residual stream, in ``residual_dtype`` where the
        # configuration names one: a branch's result is added in it
        h = norm(name="input_norm")(x)
        handed = None
        if mixer is not None:
            attention = ATTENTION_OF[mixer]
            if cfg.hybrid and mixer in HYBRID_KINDS:
                mixed = attention(cfg, name="attn")(
                    h, positions, mask, memory, depth, self.keeps)
                if self.keeps:
                    mixed, handed = mixed
            else:
                mixed = attention(cfg, name="attn")(h, positions, mask)
            if cfg.sandwich_norm:
                mixed = norm(name="attn_out_norm")(mixed)
            x = x + mixed.astype(x.dtype)
            x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
            if not has_ffn:     # a layer of one branch: the mixer alone
                return (x, handed) if self.keeps else x
            h = norm(name="post_attn_norm")(x)
        out = feed_forward(ffn_cfg, name="mlp")(h)
        if cfg.sandwich_norm:
            out = norm(name="mlp_out_norm")(out)
        x = x + out.astype(x.dtype)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        return (x, handed) if self.keeps else x


class _ScannedLayer(nn.Module):
    """DecoderLayer wrapped for nn.scan (carry=x, per-layer params; in a
    ``hybrid`` stack the memory broadcast and the layer's index scanned)."""

    config: LlamaConfig
    kind: str = "gqa"

    @nn.compact
    def __call__(self, x, positions, mask, memory=None, depth=None):
        x = DecoderLayer(self.config, self.kind, name="layer")(
            x, positions, mask, memory, depth)
        return x, None


class _KeepingLayer(nn.Module):
    """A memory layer: ``DecoderLayer`` that returns ``(x, handed)``."""

    config: LlamaConfig
    kind: str = "gqa"

    @nn.compact
    def __call__(self, x, positions, mask, depth):
        return DecoderLayer(self.config, self.kind, True, name="layer")(
            x, positions, mask, None, depth)


def _stacked(layer_cls, length, axis="layers", hybrid=False):
    """``layer_cls`` scanned ``length`` times over parameters stacked on a
    leading axis (its logical name ``axis``: no name twice in one array),
    the residual stream the carry.  ``hybrid``: the call's last argument,
    the layers' indices, is scanned beside the parameters."""
    return nn.scan(
        layer_cls,
        # what a layer sows (a routed block's loss terms and counts) stacks
        # on the layer axis beside its parameters
        # and its buffers (a router's selection bias: ``models/moe.py``)
        variable_axes={"params": 0, "losses": 0, "stats": 0, "buffers": 0},
        split_rngs={"params": True},
        # positions, mask and the memory are shared by all layers
        in_axes=(nn.broadcast,) * 3 + (0,) if hybrid else nn.broadcast,
        length=length,
        metadata_params={nn.PARTITION_NAME: axis},
    )


def _layer_class(cfg, scanned, layer=_ScannedLayer):
    """``layer``, rematerialised where the configuration says so:
    the forward pass keeps a layer's input and what its attention core's
    forward kernels, or its experts' first grouped matmuls, wrote under a
    name of ``ops/pallas/kept.py``; the backward pass computes the rest of
    the layer again."""
    if not cfg.remat:
        return layer
    return nn.remat(
        layer,
        prevent_cse=not scanned,
        static_argnums=(),
        policy=LAYER_POLICY,
    )


class _ScannedPeriod(nn.Module):
    """One period of ``layer_pattern`` wrapped for nn.scan: each run of
    equal layers a scan of its own inside, so a kind's parameters are
    stacked ``[periods, run, ...]`` under the run's name and each kind is
    traced once."""

    config: LlamaConfig
    #: ``None``: the pattern's period; else the entries of the prefix
    entries: Optional[Tuple[str, ...]] = None

    @nn.compact
    def __call__(self, x, positions, mask, memory=None, depths=None):
        cfg = self.config
        first = 0
        for name, kind, length in cfg.layer_runs(self.entries):
            more = () if depths is None else (
                memory, depths[first: first + length])
            x, _ = _stacked(_layer_class(cfg, True), length,
                            hybrid=depths is not None)(
                cfg, kind, name=name)(x, positions, mask, *more)
            first += length
        return x, None


class _MemoryLayers(nn.Module):
    """The layers of ``memory_layers``, each once, from index ``first`` of
    the stack: ``(x, (Y, K, V))``, what the cross-decoder's layers read.
    What passes on stands under ``attn.core`` / ``handed``: ``Y`` taken to
    the compute dtype and, in the backward pass, the memory's gradient
    summed over its readers."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, mask, first):
        cfg = self.config
        handed = {}
        for i, (name, kind, _) in enumerate(
                cfg.layer_runs(cfg.memory_layers)):
            x, kept = _layer_class(cfg, False, _KeepingLayer)(
                cfg, kind, name=name)(x, positions, mask, first + i)
            handed["y" if layer_kind(kind)[0] == "mamba" else "kv"] = kept
        with jax.named_scope("attn.core"), jax.named_scope("handed"):
            return x, (handed["y"].astype(cfg.dtype),) + tuple(handed["kv"])


class LMHead(nn.Module):
    """Final projection: compute-dtype operands on the MXU, fp32
    accumulation.

    With the default bf16 compute dtype the hidden states reaching this
    layer are already bf16, so an fp32 matmul (the obvious "logits must
    be fp32" spelling) only UPcasts bf16 inputs and then runs at the
    MXU's much slower fp32 rate — pure cost, zero precision gain.
    ``preferred_element_type=float32`` gets native-rate multiplies with
    fp32 accumulators and fp32 logits out: exactly what a stable
    softmax-xent needs.  At a 32k vocab this matmul is ~10% of a 1B
    model's FLOPs, so the rate difference moves whole-model MFU by
    percentage points.  (Duck-typed over any config carrying
    hidden_size/vocab_size/pred_heads/dtype/param_dtype — the MoE model
    reuses it.)  With ``pred_heads`` the one projection holds that many blocks of
    ``vocab_size`` columns, head ``i`` in columns ``[i * vocab_size, (i +
    1) * vocab_size)``.
    """

    config: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "vocab")
            ),
            (cfg.hidden_size, cfg.pred_heads * cfg.vocab_size),
            cfg.param_dtype,
        )
        # ``x`` None: the kernel alone, for a caller that reads the head
        # more than once (a looped stack's exits)
        return kernel if x is None else self.project(cfg, x, kernel)

    @staticmethod
    def project(cfg, x, kernel, transposed=False):
        """``x kernel`` (``transposed``: ``x kernel^T``, a tied head over
        the embedding table ``[vocab, hidden]``) as the class comment says."""
        return jax.lax.dot_general(
            x.astype(cfg.dtype),
            kernel.astype(cfg.dtype),
            (((x.ndim - 1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


#: the most float32 logits ``weighted_token_losses`` holds at once
HEAD_BLOCK_BYTES = 2 ** 30


def head_block_rows(batch, seq, vocab):
    """The rows of a sequence ``weighted_token_losses`` works at once: the
    largest power-of-two share of ``seq`` whose float32 logits stay under
    ``HEAD_BLOCK_BYTES`` (4096 rows of 16,384 at a vocabulary of 49,152; a
    small model's rows all at once), from the shapes alone."""
    rows = seq
    while rows % 2 == 0 and 4 * batch * rows * vocab > HEAD_BLOCK_BYTES:
        rows //= 2
    return rows


def _walk_head(cfg, x, kernel, targets, weights, transposed, with_grads):
    """``weighted_token_losses``' one walk over every exit's blocks of rows:
    ``((L, CE [T, B, S]), (gx, gK))``, the two gradients of ``L = sum
    weights * CE`` made in the same visit of a block while its logits stand
    (``with_grads``; else ``None`` both, and none of their products)."""
    T, B, S, E = x.shape
    vocab_axis = 0 if transposed else 1
    vocab = kernel.shape[vocab_axis]
    rows = head_block_rows(B, S, vocab)

    def block(grad_kernel, at):
        exit_, first = at

        # sliced from the whole inside: no second copy of it in blocks
        x_block = jax.lax.dynamic_slice(
            x, (exit_, 0, first, 0), (1, B, rows, E))[0]
        target = jax.lax.dynamic_slice_in_dim(targets, first, rows, 1)
        logits = LMHead.project(cfg, x_block, kernel, transposed)
        lse = jax.nn.logsumexp(logits, axis=-1)
        losses = lse - jnp.take_along_axis(
            logits, target[..., None], axis=-1)[..., 0]
        if not with_grads:
            return grad_kernel, (losses, None)
        # float32 beside operands in the compute dtype, as the transpose of
        # ``LMHead.project`` hands its two products their cotangent
        d = jax.lax.dynamic_slice(
            weights, (exit_, 0, first), (1, B, rows))[0][..., None] * (
            jnp.exp(logits - lse[..., None])
            - jax.nn.one_hot(target, vocab, dtype=jnp.float32))
        grad_x = jax.lax.dot_general(
            d, kernel, (((2,), (vocab_axis,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
        # [hidden, vocab], a tied table's [vocab, hidden]
        ahead, behind = (d, x_block) if transposed else (x_block, d)
        return grad_kernel + jax.lax.dot_general(
            ahead, behind, (((0, 1), (0, 1)), ((), ())),
            preferred_element_type=jnp.float32), (losses, grad_x)

    blocks = S // rows
    grad_kernel, (losses, grad_x) = jax.lax.scan(
        block, jnp.zeros(kernel.shape, jnp.float32) if with_grads else None,
        (jnp.repeat(jnp.arange(T), blocks),
         jnp.tile(jnp.arange(0, S, rows), T)))

    def whole(by_block):    # [T * blocks, B, rows, ..] -> [T, B, S, ..]
        rest = by_block.shape[3:]
        return jnp.moveaxis(
            by_block.reshape((T, blocks, B, rows) + rest), 1, 2).reshape(
                (T, B, S) + rest)

    losses = whole(losses)
    return (jnp.sum(weights * losses), losses), (
        whole(grad_x) if with_grads else None, grad_kernel)


@partial(jax.custom_vjp, nondiff_argnums=(0, 5))
def _weighted_head(cfg, x, kernel, targets, weights, transposed):
    """``weighted_token_losses`` of operands in the compute dtype; called
    without differentiation, the walk for ``L`` and ``CE`` alone."""
    return _walk_head(cfg, x, kernel, targets, weights, transposed, False)[0]


def _weighted_head_fwd(cfg, x, kernel, targets, weights, transposed):
    out, grads = _walk_head(
        cfg, x, kernel, targets, weights, transposed, True)
    return out, grads + (out[1],)


def _weighted_head_bwd(cfg, transposed, kept, cotangents):
    grad_x, grad_kernel, losses = kept
    g, _ = cotangents       # a scalar; ``CE``'s cotangent is not read
    return ((g * grad_x).astype(cfg.dtype),
            (g * grad_kernel).astype(cfg.dtype), None, g * losses)


_weighted_head.defvjp(_weighted_head_fwd, _weighted_head_bwd)


def weighted_token_losses(cfg, x, kernel, targets, weights, transposed=False):
    """``(L, CE)`` of a looped stack's exits: ``CE = -log softmax(x
    kernel)[targets]``, ``[T, B, S]`` float32, of every exit's normed
    stream ``x [T, B, S, E]`` under the one head (``LMHead.project``;
    ``transposed``: a tied table ``[vocab, hidden]``), and ``L = sum weights
    * CE``, a float32 scalar, ``weights [T, B, S]`` float32.  ``[B, S,
    vocab]`` never stands whole: the head and its cross entropy run a block
    of ``head_block_rows`` rows at a time, every exit's blocks in one walk.

    ``L`` is what is differentiated, and its gradient is taken in the
    FORWARD pass: a block's ``d = weights * (softmax - onehot)`` and its two
    products ``d kernel^T`` (``x``'s gradient, in the compute dtype) and
    ``x^T d`` (the kernel's, summed over blocks and exits in float32 and
    rounded to the compute dtype once) are made while the block's logits
    stand, so no block's logits are made a second time and what is kept for
    the backward pass is those two gradients and ``CE`` (the weights'
    gradient); the backward rule multiplies the three by ``L``'s scalar
    cotangent.  ``CE`` is for reading only: the rule gives it NO gradient,
    and a caller takes it through ``stop_gradient``.  A call that is not
    differentiated walks the same blocks for ``L`` and ``CE`` and makes
    neither product."""
    return _weighted_head(
        cfg, x.astype(cfg.dtype), kernel.astype(cfg.dtype), targets, weights,
        transposed)


class ExitGate(nn.Module):
    """A looped stack's exit gate: the logit of ``lam = sigmoid(h w_g +
    b_g)``, one scalar a token, ``[B, S]`` float32 (accumulated in float32
    from operands in the compute dtype, as the head's logits are)."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        kernel = self.param(
            "kernel", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", None)),
            (cfg.hidden_size, 1), cfg.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (1,),
                          cfg.param_dtype)
        # operands in the stream's dtype, so that what the backward pass
        # keeps of a loop step is the stream itself and no float32 copy
        return jnp.einsum(
            "bse,e->bs", x, kernel[:, 0].astype(x.dtype),
            preferred_element_type=jnp.float32) + bias[0].astype(jnp.float32)


def exit_distribution(gate_logits):
    """``log p_t`` ``[T, ...]`` from the gates' logits ``[T, ...]``: ``p_t =
    lam_t prod_{j<t} (1 - lam_j)`` for ``t < T`` and ``p_T = prod_{j<T} (1 -
    lam_j)``, the mass no earlier exit took (the last gate's own value
    decides nothing), in logarithms so that no product underflows."""
    stay = jax.nn.log_sigmoid(-gate_logits)
    before = jnp.cumsum(stay, axis=0) - stay        # sum over j < t
    return jnp.concatenate([
        before[:-1] + jax.nn.log_sigmoid(gate_logits[:-1]), before[-1:]])


class LlamaForCausalLM(nn.Module):
    """Decoder-only LM head model.

    Citation (behavioral parity target): the reference trains this family
    via Megatron/DeepSpeed (e.g. examples and flash-ckpt engines,
    ``dlrover/trainer/torch/flash_checkpoint/megatron.py``); here the model
    is native and the checkpoint/elastic machinery attaches to it directly.
    """

    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        B, S = input_ids.shape
        embed = self.param(
            "embed_tokens",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.hidden_size),
            cfg.param_dtype,
        )
        rows = input_ids
        if cfg.block_diffusion:
            rows, weights = self._noisy_and_clean(input_ids)
        with jax.named_scope("embed"):
            x = embed.astype(cfg.dtype)[rows]
            if cfg.residual_dtype is not None:
                x = x.astype(cfg.residual_dtype)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        if cfg.block_diffusion:     # each copy of the sequence counts 0..S-1
            positions = jnp.concatenate([positions, positions], axis=1)
        # only the reference core reads a mask: an indexer's attention makes
        # its own, a block at a time, so does the attention over windows and
        # summaries, the kernel is causal by position and a ``kda`` layer
        # has no scores
        mask = None if (
            cfg.index_topk or cfg.eva_window or cfg.attention_impl == "flash"
            or cfg.block_diffusion
            or (cfg.layer_pattern and not {"gqa", "swa", "xattn"} & {
                layer_kind(entry)[0] for entry in cfg.layer_kinds()})
        ) else jnp.tril(jnp.ones((S, S), dtype=bool))[None, None, :, :]

        if cfg.loop_steps > 1:
            return self._looped_stack(x, positions, mask, input_ids)
        if cfg.hybrid:
            x = self._hybrid_stack(x, positions, mask)
        elif cfg.layer_pattern:
            if cfg.layer_prefix:    # once, before the periods
                x, _ = _ScannedPeriod(cfg, cfg.layer_prefix, name="prefix")(
                    x, positions, mask)
            # a scan over periods, each run of equal layers a scan inside
            # (``scan_layers`` does not apply: a pattern is always stacked)
            x, _ = _stacked(_ScannedPeriod, cfg.periods, "periods")(
                cfg, name="layers")(x, positions, mask)
            if cfg.layer_suffix:    # once, after the periods
                x, _ = _ScannedPeriod(cfg, cfg.layer_suffix, name="suffix")(
                    x, positions, mask)
        elif cfg.scan_layers:
            x, _ = _stacked(_layer_class(cfg, True), cfg.num_layers)(
                cfg, name="layers")(x, positions, mask)
        else:
            layer_cls = _layer_class(cfg, False)
            for i in range(cfg.num_layers):
                x, _ = layer_cls(cfg, name=f"layers_{i}")(x, positions, mask)

        if cfg.block_diffusion:     # the clean half yields no logits
            x = x[:, :S]
        x = _norm_of(cfg)(name="final_norm")(x)
        if cfg.tie_embeddings:
            with jax.named_scope("lm_head"):
                logits = LMHead.project(cfg, x, embed, transposed=True)
        else:
            logits = LMHead(cfg, name="lm_head")(x)
        if cfg.block_diffusion:
            with jax.named_scope("head_loss"):
                self._sow_nelbo(logits, input_ids, weights)
        if cfg.pred_heads > 1:
            with jax.named_scope("head_loss"):
                logits = self._first_head(logits, input_ids)
        return nn.with_logical_constraint(logits, ("batch", "seq", "vocab"))

    def _hybrid_stack(self, x, positions, mask):
        """A patterned stack whose layers are handed the memory and their
        own index: the prefix once, the periods of ``layer_pattern``, then
        (a decoder-hybrid-decoder stack) the memory layers once and the
        periods of ``cross_pattern``, every layer of which reads what the
        memory layers handed on: a broadcast operand of the scan over
        periods, its gradient the sum over the readers."""
        cfg = self.config
        if not cfg.layer_pattern:
            raise ValueError("diff_attention needs a layer_pattern")
        first = 0

        def periods_of(entries, periods, name, memory=None):
            nonlocal first
            depths = first + jnp.arange(
                periods * len(entries), dtype=jnp.int32).reshape(
                    periods, len(entries))
            first += periods * len(entries)
            return _stacked(_ScannedPeriod, periods, "periods", True)(
                cfg, entries, name=name)(x, positions, mask, memory, depths)[0]

        if cfg.layer_prefix:
            x, _ = _ScannedPeriod(cfg, cfg.layer_prefix, name="prefix")(
                x, positions, mask, None,
                jnp.arange(len(cfg.layer_prefix), dtype=jnp.int32))
            first = len(cfg.layer_prefix)
        x = periods_of(cfg.layer_pattern, cfg.periods, "layers")
        if cfg.memory_layers:
            x, memory = _MemoryLayers(cfg, name="memory")(
                x, positions, mask, jnp.int32(first))
            first += len(cfg.memory_layers)
            if cfg.cross_periods:
                self.sow("stats", "memory_readers", jnp.int32(
                    cfg.cross_periods * len(cfg.cross_pattern)))
                x = periods_of(cfg.cross_pattern, cfg.cross_periods, "cross",
                               memory)
        return x

    def _looped_stack(self, x, positions, mask, input_ids):
        """The stack ``loop_steps`` times over the same parameters, the
        final norm inside the loop; the logits ``z_T`` of the last loop
        step's stream.  One ``nn.scan`` over loop steps whose parameters are
        BROADCAST (the tree is the plain model's: ``layers/layer/...``
        stacked on the layer axis, ``final_norm``, ``lm_head``), around the
        scan over layers; a weight's gradient is the sum of its uses',
        taken by the scan's transpose in the cotangent's dtype (under a
        trainer's ``grads_dtype`` bfloat16: bfloat16).  Each layer is
        rematerialised as ever: the forward pass keeps ``loop_steps x
        num_layers`` layer inputs.

        With ``exit_gate`` a loop step also reads the gate and hands out
        (the scan's ``ys``) its normed stream and its gate's logits, ``[B, S,
        E]`` in the compute dtype and ``[B, S]``; the heads run AFTER the
        loop, all exits' in one walk (``_sow_exit_objective``), so that the
        forward pass ends on them and the backward pass starts from their
        gradients, made already."""
        cfg = self.config
        kernel = LMHead(cfg, name="lm_head")(None)

        def loop_step(model, x, _):
            x, _ = _stacked(_layer_class(cfg, True), cfg.num_layers)(
                cfg, name="layers")(x, positions, mask)
            # rematerialised: a loop step keeps the stack's output in the
            # compute dtype, not the norm's float32 intermediates (two
            # [B, S, E] float32 arrays a loop step)
            x = nn.remat(
                lambda model, x: _norm_of(cfg)(name="final_norm")(x),
                prevent_cse=False)(model, x)
            if not cfg.exit_gate:
                return x, None
            with jax.named_scope("head_loss"), jax.named_scope("exit"):
                return x, (x, ExitGate(cfg, name="exit_gate")(x))

        x, exits = nn.scan(
            loop_step, variable_broadcast="params",
            split_rngs={"params": False}, length=cfg.loop_steps)(
                self, x, None)
        if cfg.exit_gate:
            with jax.named_scope("head_loss"):
                self._sow_exit_objective(*exits, kernel, input_ids)
        with jax.named_scope("lm_head"):
            logits = LMHead.project(cfg, x, kernel)
        return nn.with_logical_constraint(logits, ("batch", "seq", "vocab"))

    def _sow_exit_objective(self, streams, gate_logits, kernel, input_ids):
        """The model's objective into ``losses`` from every exit's normed
        stream ``[T, B, S, E]`` and gate logits ``[T, B, S]``: ``mean_i
        [sum_t p_t,i CE_t,i - beta H(p_.,i)]`` over the positions that have
        a target (all but a sequence's last), ``H`` the exit distribution's
        entropy in nats.  The weight of a token's loss at an exit, ``p_t,i /
        N`` (0 where there is no target), is known once the gates are read,
        so the four heads are ONE call of ``weighted_token_losses``, whose
        gradient is taken as its forward pass walks the blocks; the gates'
        gradient reaches them through the weights and through the entropy.
        Into ``stats`` the mean entropy, the mean mass of the last exit and
        each exit's mean cross entropy.  A caller that asks for the
        collection ``exits`` is handed ``CE`` and ``log p`` token by token
        (``token_losses`` and ``log_p``: the comparison with the reference
        reads them; nothing in training does).  ``CE`` out of the weighted
        head carries no gradient: it is read here, never differentiated."""
        cfg = self.config
        T, B, S, _ = streams.shape
        has_target = jnp.arange(S) < S - 1

        def mean(per_token):     # over [.., B, S]'s last two axes
            return jnp.sum(jnp.where(has_target, per_token, 0.0),
                           axis=(-2, -1)) / (B * (S - 1))

        with jax.named_scope("exit"):
            log_p = exit_distribution(gate_logits)
            p = jnp.exp(log_p)
            weights = jnp.where(has_target, p, 0.0) / (B * (S - 1))
        # position i's target is token i + 1; the last has none (weight 0)
        targets = jnp.roll(input_ids, -1, axis=1)
        rows = head_block_rows(B, S, kernel.shape[-1])
        trace.note_trace_time("head.path", exits=T, rows=rows,
                              blocks=T * S // rows, grad="forward")
        expected, losses = weighted_token_losses(
            cfg, streams, kernel, targets, weights)
        losses = jax.lax.stop_gradient(losses)
        with jax.named_scope("exit"):
            entropy = -jnp.sum(p * log_p, axis=0)
            self.sow("exits", "token_losses", losses)
            self.sow("exits", "log_p", log_p)
            self.sow("losses", "exit_objective",
                     expected - cfg.exit_entropy_weight * mean(entropy))
            self.sow("stats", "loop_exit_entropy", mean(entropy))
            self.sow("stats", "loop_exit_mass_last", mean(p[-1]))
            self.sow("stats", "loop_ce_by_step", mean(losses))

    def _noisy_and_clean(self, input_ids):
        """``([noisy copy ; clean copy] [B, 2S], the NELBO's weights [B,
        S])`` from the step's ``noise`` stream, step 0's where the caller
        gave none."""
        cfg = self.config
        key = (self.make_rng("noise") if self.has_rng("noise")
               else cfg.step_rngs(0)["noise"])
        with jax.named_scope("embed"), jax.named_scope("noise"):
            noisy, weights = noise_blocks(
                input_ids, key, cfg.block_diffusion, cfg.mask_token_id,
                cfg.noise_eps)
            self.sow("stats", "bd_masked_share", jnp.mean(weights > 0))
            self.sow("stats", "bd_weight_max", jnp.max(weights))
            return jnp.concatenate([noisy, input_ids], axis=1), weights

    def _sow_nelbo(self, logits, input_ids, weights):
        """The objective's first term into ``losses``: ``(1 / (B S)) sum_i
        m_i / t_b(i) CE(logits of noisy row i, the clean token AT position
        i)``: no shift, and nothing from a token left unmasked."""
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        taken = jnp.take_along_axis(logp, input_ids[..., None], axis=-1)[..., 0]
        self.sow("losses", "nelbo", -jnp.mean(weights * taken))

    def _first_head(self, logits, input_ids):
        """The first prediction head's logits ``[B, S, vocab]``; the loss
        of the others is sown into ``losses``: head ``i`` (1 on) at
        position ``t`` predicts ``input_ids[t + 1 + i]``, its cross entropy
        averaged over the positions that have such a token, the heads'
        terms summed unweighted (arXiv:2404.19737, equation 2).  The model
        sees no labels, so each of these heads goes without the one target
        that lies beyond ``input_ids``; the first head's loss is the
        caller's, on its labels."""
        cfg = self.config
        B, S = input_ids.shape
        logits = logits.reshape(B, S, cfg.pred_heads, cfg.vocab_size)
        later = jnp.float32(0)
        for i in range(1, min(cfg.pred_heads, S - 1)):
            logp = jax.nn.log_softmax(logits[:, : S - 1 - i, i], axis=-1)
            taken = jnp.take_along_axis(
                logp, input_ids[:, 1 + i:, None], axis=-1)
            later = later - jnp.mean(taken)
        self.sow("losses", "multi_byte", later)
        self.sow("stats", "multi_byte_loss", later)
        return logits[:, :, 0]

    def num_params(self) -> int:
        cfg = self.config
        def softmax(kind):
            heads = cfg.attention_numbers(kind).heads
            attn = cfg.hidden_size * cfg.head_dim * (
                heads * 2 + cfg.num_kv_heads * 2
            )
            if cfg.qk_norm == "head":
                attn += 2 * cfg.head_dim
            elif cfg.qk_norm:
                attn += cfg.head_dim * (heads + cfg.num_kv_heads)
            if cfg.index_topk:  # q, k and weight projections, the LayerNorm
                attn += cfg.hidden_size * (
                    cfg.index_heads * (cfg.index_head_dim + 1)
                    + cfg.index_head_dim) + 2 * cfg.index_head_dim
            if cfg.eva_window:      # the two pooling vectors a head
                attn += 2 * heads * cfg.head_dim
            if cfg.attn_gate:       # the output gate's projection
                attn += cfg.hidden_size * heads * cfg.head_dim
            if cfg.attn_head_gate:  # one column a head
                attn += cfg.hidden_size * heads
            return attn

        if cfg.diff_attention:
            softmax = partial(Attention.differential_params, cfg)
        by_kind = {"gqa": lambda: softmax("gqa"),
                   "swa": lambda: softmax("swa"),
                   "xattn": lambda: softmax("xattn"),
                   "kda": lambda: DeltaAttention.num_params(cfg),
                   "mla": lambda: LatentAttention.num_params(cfg),
                   "mamba": lambda: MambaMixer.num_params(cfg),
                   "gmu": lambda: GatedMemoryUnit.num_params(cfg),
                   "mamba2": lambda: Mamba2Mixer.num_params(cfg),
                   "conv": lambda: ShortConvMixer.num_params(cfg)}
        # a norm's parameters: a scale, with ``norm`` "layer" a bias too
        norm = cfg.hidden_size * (2 if cfg.norm == "layer" else 1)

        def layers(entries):
            total = 0
            for entry in entries:
                dense = layer_kind(entry)[1]
                mixer, has_ffn = layer_branches(entry)
                # a branch: its norm, under ``sandwich_norm`` one more
                branches = (mixer is not None) + has_ffn
                total += (2 if cfg.sandwich_norm else 1) * branches * norm
                if mixer is not None:
                    total += by_kind[mixer]()
                if has_ffn:
                    total += (cfg.mlp_matrices * cfg.hidden_size
                              * cfg.dense_intermediate_size
                              if dense else cfg.feed_forward_params())
            return total

        entries = (cfg.layer_kinds() if cfg.layer_pattern
                   else ("gqa",) * cfg.num_layers)
        tables = cfg.pred_heads + (0 if cfg.tie_embeddings else 1)
        # a looped stack's weights count once; its exit gate: a column
        # and a bias
        gate = cfg.hidden_size + 1 if cfg.exit_gate else 0
        return (cfg.vocab_size * cfg.hidden_size * tables
                + layers(entries) + norm + gate)
