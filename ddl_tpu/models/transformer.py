"""Decoder-only Transformer LM — the long-context model family.

The reference trains exactly one model family, a CNN classifier
(``single.py:297-299``), whose parallelism surface is DP x PP.  This module
is the capability the reference's design cannot express: a sequence model
whose sharding exercises every remaining mesh axis — tensor parallelism
(attention heads / MLP hidden / vocab over ``model``), sequence/context
parallelism (ring attention over ``seq``, ``parallel/ring_attention.py``),
expert parallelism (MoE expert dimension over ``expert``), and FSDP-style
parameter sharding (over ``data``) — all expressed as logical axis
annotations resolved by the rule table in ``parallel/sharding.py``.

Architecture: pre-RMSNorm blocks, rotary position embeddings, causal
attention, GELU MLP or a GShard-style top-k mixture-of-experts with token
capacity and a load-balancing auxiliary loss.  Params are float32 masters
with bfloat16 compute (TPU MXU-native); the loss-side logits are returned in
float32.

What a layer is can also be said layer by layer (``LMConfig.layer_types``
and the fields beside it): sliding-window and full-attention layers mixed,
rotary positions only in the sliding ones, RMSNorm on each head's q and k,
a sigmoid gate on the attention output, four norms a block, a gated
three-matrix MLP, leading dense layers, and a dropless expert layer
(sigmoid scores, biased top-k selection, a shared expert, the grouped
product of ``ops/grouped_matmul.py``) that is told which experts it holds
(``expert_share``), routes over all of them and computes its own experts'
part.  That is the afmoe block of ``benchmark/configs/trinity-mini.json``;
the defaults are the block above, unchanged.

The expert layer is said in two statements: how a token scores the experts
(``moe_router``: softmax or sigmoid) and what happens to its choices
(``moe_layer``: a token capacity, or dropless).  Softmax scores through the
dropless layer, every MLP sparse, no shared expert, and rotary positions
said by kind of layer (``rope_by_kind``: plain in the sliding layers,
YaRN-scaled in the full ones, ``Rope``) are the mellum block of
``benchmark/configs/mellum2-12b-a2.5b.json``.

A layer's mixer need not be attention at all (``layer_types`` again): a
Mamba-1 layer (``Mamba``: causal depthwise convolution, the selective scan
of ``ops/selective_scan.py``, a gate), a Gated Memory Unit that gates an
earlier Mamba layer's scan output (``Gmu``), differential attention over
head pairs (``DiffAttention``), and cross-attention over an earlier full
layer's K and V.  The two values that travel down the stack are an
explicit argument and result of ``Block`` (``carry``), so a rematerialised
layer is recomputed from them and their layers are not.  LayerNorm with
bias for RMSNorm, no positions, and a head tied to the embedding complete
the SambaY stack of ``benchmark/configs/phi4-mini-flash.json``.

A ``conv`` layer's mixer is a gated short convolution (``ShortConv``):
``[B, C, x]`` from one projection, ``C * conv(B * x)`` with a causal
depthwise convolution of ``short_conv`` taps and no bias, then an out
projection.  Conv layers beside full-attention ones with q/k norms and
plain rotary, sigmoid-scored dropless experts selected with a bias and
normalised with ``route_eps``, a leading dense layer and a tied head are
the lfm2_moe stack of ``benchmark/configs/lfm2-8b-a1b.json``.

Latent attention (``Mla``, where ``kv_lora_rank`` is set) takes the place
of ``Attention``: K and V projected up from one shared latent behind its
own RMSNorm, and a rotary key part shared by every head beside each
query's own rotary part (interleaved pairs, ``Rope(interleave=True)``);
Q and K are wider than V.  With a leading dense layer, sigmoid-scored
dropless experts selected with a bias beside two shared experts' width,
a routed scaling factor and an untied head it is the deepseek_v3 stack
of ``benchmark/configs/kanana-2-30b-a3b.json``.

No torch/CUDA analog exists in the reference; parity citations therefore
point at the subsystems this family plugs into: the mesh backbone
(SURVEY.md §2 C10), the trainer (C3), and the checkpointing layout (C8).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ddl_tpu.ops.attention import dense_attention

__all__ = [
    "LAYER_KINDS",
    "LMConfig",
    "Mla",
    "Rope",
    "REMAT_POLICIES",
    "TransformerLM",
    "count_lm_params",
    "dropless_plan",
    "make_embed",
    "make_lm_head",
    "apply_final_norm_and_head",
    "moe_routing_plan",
    "remat_block",
]


LAYER_KINDS = ("sliding_attention", "full_attention", "mamba", "gmu",
               "cross_attention", "conv")
# {a kind of layer that reads a carried value: the kind that keeps it}
_CARRIED = {"gmu": "mamba", "cross_attention": "full_attention"}


@dataclasses.dataclass(frozen=True)
class Rope:
    """One kind of layer's rotary positions: plain (``factor`` 1:
    ``inv_freq_i = theta^(-i/half)``) or YaRN (Peng et al. 2023, as the
    ``transformers`` ``rope_type: yarn`` computes it): the plain
    frequencies ``ext_i`` and the interpolated ones ``ext_i / factor``
    blended by a ramp over the frequency index, ``int_i r_i + ext_i (1 -
    r_i)`` with ``r_i = clip((i - low) / (high - low), 0, 1)``, ``low``
    and ``high`` the indices whose wavelengths make ``beta_fast`` and
    ``beta_slow`` turns over ``original`` positions; cos and sin times
    ``attention_factor``.  ``interleave`` pairs a head's dims ``(2i,
    2i + 1)`` and rotates each pair in place, as DeepSeek's
    ``rope_interleave`` weights are laid out; otherwise pair ``i`` is
    ``(i, i + half)``, the half-split layout."""

    theta: float = 10000.0
    factor: float = 1.0
    original: int = 0  # positions the model was first trained at
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    interleave: bool = False

    def __post_init__(self):
        if self.factor < 1.0 or (self.factor > 1.0 and self.original <= 0):
            raise ValueError(
                f"a scaled rotary needs factor >= 1 and the original length "
                f"it scales from, got {self!r}"
            )

    def ramp_ends(self, head_dim: int) -> tuple[int, int]:
        """``(low, high)``: the frequency indices between which YaRN's
        ramp goes from the plain frequencies to the interpolated ones."""
        def index(turns):
            return (head_dim * math.log(self.original / (turns * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        return (max(math.floor(index(self.beta_fast)), 0),
                min(math.ceil(index(self.beta_slow)), head_dim - 1))

    def inv_freq(self, half: int):
        """(half,) float32 angular frequencies a position."""
        ext = self.theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        if self.factor == 1.0:
            return ext
        low, high = self.ramp_ends(2 * half)
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 0.001),
            0.0, 1.0,
        )
        return (ext / self.factor) * ramp + ext * (1.0 - ramp)


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """What the LM is, field by field; the defaults are the plain block
    (pre-RMSNorm, rotary, GELU MLP).  ``layer_types`` names each layer's
    mixer (``LAYER_KINDS``): sliding or full attention, a Mamba layer, a
    GMU, cross-attention, or a gated short convolution (``conv``, taps
    ``short_conv``); the fields after it say the rest of a layer."""

    vocab_size: int = 256  # byte-level by default
    d_model: int = 256
    n_layers: int = 4
    n_heads: int = 8
    head_dim: int = 32
    # Grouped-query attention: number of K/V heads (0 = n_heads, i.e.
    # classic multi-head).  Each K/V head serves n_heads/n_kv_heads query
    # heads — smaller K/V projections and an n_heads/n_kv_heads-times
    # smaller decode cache (the Llama-2/Mistral recipe).  Must divide
    # n_heads; with tensor parallelism it must also divide by the model
    # axis so every shard holds whole K/V heads.
    n_kv_heads: int = 0
    d_ff: int = 1024
    # MoE: 0 = dense MLP in every block; >0 = every block is a top-k MoE
    # with this many experts.
    num_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.5
    # Post-warm-up capacity target.  An UNTRAINED router drops a third of
    # its token-choices at cf 1.0 (measured: drop-frac 0.36 -> 0.005 over
    # 400 steps as the aux loss balances load, training_logs/lm-moe-r4),
    # so capacity_factor keeps warm-up headroom — but a CONVERGED router
    # doesn't need it, and the extra slots are pure dispatch/FFN overhead
    # (cf 1.5 taxes the step −20% vs the dense MLP, cf 1.0 −12.7%;
    # PERF.md MoE table).  The trainer (train/lm_trainer.py) anneals
    # capacity_factor down to this value once the LIVE ``moe_drop_frac``
    # metric stays under ``capacity_anneal_drop`` (one recompile at the
    # switch; params/optimizer state are capacity-independent).  Set equal
    # to capacity_factor (or >= it) to disable annealing.
    capacity_factor_min: float = 1.0
    # Router drop fraction below which capacity anneals to
    # capacity_factor_min (checked at each trainer logging period).
    # Caveat: the pipeline-parallel step metrics do not surface
    # ``moe_drop_frac`` (router stats are sown inside the manual pipe
    # region), so metric-driven annealing is inert there — pipelined MoE
    # runs should set ``capacity_anneal_step`` instead.
    capacity_anneal_drop: float = 0.02
    # Step-count fallback for the anneal (0 = off): anneal at this
    # optimizer step regardless of the metric — for paths that don't
    # surface the live drop fraction (pipeline parallelism), sized from
    # the measured router convergence (~400 steps on the round-4 corpus
    # run, training_logs/lm-moe-r4).
    capacity_anneal_step: int = 0
    # How the expert-parallel exchange is issued when the mesh has an
    # expert axis: 'gspmd' lets the partitioner insert the collectives
    # for the dispatch/combine resharding (batch is sharded over
    # (data, expert); the expert-sharded slots force an all-to-all);
    # 'alltoall' issues it manually — a partial-manual shard_map over
    # 'expert' around per-shard sort-dispatch, lax.all_to_all of the
    # capacity slots to the expert owners, local expert FFN, and the
    # reverse exchange (the GShard/Switch production path, exact-parity
    # with the GSPMD path).  'auto' (default) resolves to 'alltoall' on
    # an expert axis > 1 and 'gspmd' otherwise.
    moe_ep: str = "auto"
    # How tokens reach their experts.  'einsum' materialises (B, S, E, C)
    # one-hot dispatch/combine tensors and moves data with matmuls; 'sort'
    # routes with argsort index math + permutation gathers (custom-VJP:
    # the backward is also gathers, never a TPU scatter-add).  Measured on
    # one v5e chip at B=16 T=1024 E=8 top-2 (PERF.md MoE table): einsum
    # 2.9 ms vs sort 4.9 ms per dispatch+combine pair — the MXU crunches
    # one-hot matmuls faster than the gather unit moves rows, so einsum
    # wins at training scale; but its one-hot tensors grow as
    # O(B*S^2*k*cf), so at long sequence the memory (and matmul FLOPs)
    # blow up while sort's index arrays stay O(B*S*k).  'auto' (default)
    # picks einsum when the routing group is <= 2048 tokens and sort
    # beyond.
    moe_dispatch: str = "auto"
    # Routing-group size in tokens (the GShard group): capacity is
    # enforced per group, and the einsum dispatch/combine cost is
    # O(group) per token — splitting a sequence into G groups divides the
    # one-hot tensors AND their matmul FLOPs by G (measured 7x cheaper at
    # 256 vs 1024, PERF.md MoE table).  Smaller groups drop more tokens
    # at equal capacity_factor (fewer tokens to average over); 0 routes
    # the whole sequence as one group.
    moe_group: int = 256
    moe_aux_weight: float = 0.01
    rope_theta: float = 10000.0
    compute_dtype: str = "bfloat16"
    # 'dense': plain softmax attention, XLA partitions it (fine for short
    # sequences).  'ring': ppermute ring over the seq axis, memory
    # O(T_local^2) (parallel/ring_attention.py).  'ulysses': all-to-all
    # head/sequence exchange, unmodified attention per head group
    # (parallel/ulysses.py).  The manual cores are injected via
    # ``TransformerLM(attn_core=...)`` by ``train/lm_steps.py``.
    attn_impl: str = "dense"
    # Use the Pallas flash-attention kernel (ops/flash_attention.py) as the
    # per-device attention: with 'dense' it replaces the O(T^2) score
    # materialisation (requires seq mesh axis 1), with 'ulysses' it runs on
    # each head group after the all-to-all.  'ring' is already blockwise.
    # "auto" picks per run: flash when the training sequence length is at
    # or past the measured crossover and the composition supports the
    # kernel, dense otherwise (resolved by train/lm_steps.py against the
    # run's seq_len; PERF.md records the crossover measurements).
    flash: bool | str = False
    # Sliding-window attention (the Mistral recipe): each position attends
    # only the last attn_window positions (0 = unbounded causal history).
    # Requires causal=True.  Supported by the dense core, the flash kernel
    # (band-masked block skip), Ulysses (full sequence per head group),
    # the dense-block ring (global-position band across ring hops),
    # flash-in-ring (per-hop banded kernel via its kv_offset, ring
    # truncated to O(window) hops), and the decode cache.
    attn_window: int = 0
    remat: bool = True
    # What the per-block jax.checkpoint may keep instead of recomputing
    # (active only with remat=True): 'full' recomputes everything (minimum
    # memory), 'dots' saves matmul outputs (jax.checkpoint_policies
    # .checkpoint_dots — recompute only the cheap elementwise work),
    # 'dots_no_batch' saves only contraction results with no batch dims
    # (weights-stationary intermediates).  A speed/HBM dial: 'dots' trades
    # activation memory back for backward-pass FLOPs.
    remat_policy: str = "full"
    fsdp: bool = False
    # False = bidirectional attention (encoder use, e.g. the ViT family —
    # models/vit.py); LM training/decoding requires the causal default.
    causal: bool = True
    # Residual dropout after the attention and MLP sublayers (0 = off; adds
    # no parameters, so checkpoints are layout-compatible either way).
    # Training passes deterministic=False + a 'dropout' rng; eval/decode
    # leave the default deterministic=True.
    dropout_rate: float = 0.0
    # Chunked head+CE fusion (0 = off): the train/eval loss scans over
    # chunks of this many sequence positions, so the (B, T, V) logits are
    # never materialised — peak loss-edge memory drops T/ce_chunk times
    # for ~one extra head matmul of backward FLOPs (jax.checkpoint).  The
    # big-vocab lever: at V=50304, T=1024 the logits are the largest
    # tensor in the step.  Requires mesh seq=1 (chunking splits T; under
    # sequence parallelism per-device logits are already T/seq smaller).
    ce_chunk: int = 0
    # Vocab-streamed head+CE (0 = off): the loss edge scans VOCAB blocks
    # of this size with an online logsumexp, so the (B, T, V) logits
    # never exist in either direction (ops/losses.fused_vocab_chunked_ce
    # — hand-written VJP).  The extreme-vocab lever: measured ~5% slower
    # than dense CE at V=50k (PERF.md round 4) but the only loss edge
    # whose transient memory is O(B*T*vb) with no O(T*V) tensor at all.
    # Mutually exclusive with ce_chunk; requires mesh model=1 (the scan
    # slices the head kernel over vocab).
    ce_vocab_chunk: int = 0
    # --- what a layer is, layer by layer (defaults: the block above) ---
    # Attention kind of each layer, "sliding_attention" (the last
    # attn_window positions) or "full_attention" (all of the past);
    # () = every layer alike, windowed iff attn_window.  With a pattern
    # attn_window is the sliding layers' window, and only the sliding
    # layers rotate q and k (rope_theta): a full_attention layer of a
    # pattern has no positional signal of its own, unless rope_by_kind
    # gives it one.
    layer_types: tuple = ()
    # Rotary positions said by kind of layer: ((kind, Rope or None), ...)
    # for "sliding_attention" and/or "full_attention".  A kind named here
    # rotates q and k by its own Rope (plain, or YaRN-scaled), or not at
    # all (None); a kind not named keeps the rule above.
    rope_by_kind: tuple = ()
    # RMSNorm over each head's head_dim on q and k (learned scale) before
    # the rotation; a sigmoid gate on the attention output from a
    # projection of the block's input (d_model -> n_heads * head_dim).
    qk_norm: bool = False
    attn_gate: bool = False
    # Three-matrix gated MLP, (silu(x Wg) * (x Wi)) Wo, for the two-matrix
    # GELU one; applies to the dense MLP and to every expert.
    mlp_gated: bool = False
    # Four norms a block: x + norm(attn(norm(x))), x + norm(mlp(norm(x))).
    sandwich_norm: bool = False
    norm_eps: float = 1e-6
    # h0 = E[tokens] * sqrt(d_model)
    embed_scale: bool = False
    # With num_experts > 0, the leading layers that keep the dense MLP.
    num_dense_layers: int = 0
    # How a token scores the experts: 'softmax' over all of them, or
    # 'sigmoid' of each, with selection by score + a bias leaf that is not
    # trained (the leaf exists under 'sigmoid' only).
    moe_router: str = "softmax"
    # What happens to the top-k choices: 'capacity': a token capacity an
    # expert, choices past it dropped, a balance loss (the GShard path
    # above with its own knobs, moe_dispatch, moe_ep, moe_group and
    # capacity_*; softmax scores only); 'dropless': every choice computed
    # (rows sorted by expert, ops/grouped_matmul.py), the chosen scores
    # normalised to sum to 1 and scaled by route_scale, beside
    # num_shared_experts experts that every token passes through, no
    # balance loss.  '' = the score function's first pairing: 'capacity'
    # under 'softmax', 'dropless' under 'sigmoid'.
    moe_layer: str = ""
    moe_d_ff: int = 0  # an expert's width (0 = d_ff)
    # the four below are the dropless layer's, under either score; the
    # chosen scores are normalised as picked / (sum of picked + route_eps)
    num_shared_experts: int = 0
    route_scale: float = 1.0
    route_eps: float = 1e-20
    # (share index, shares): this program holds experts [i * E / n,
    # (i + 1) * E / n) of each layer's num_experts, routes over all of
    # them and computes its own experts' part of the result; choices of
    # experts held elsewhere add nothing here (their owners' exchange is
    # not this program's).  (0, 1) holds them all.
    expert_share: tuple = (0, 1)
    # --- a hybrid stack: layer_types may also name "mamba" (a Mamba-1
    # mixer), "gmu" (a Gated Memory Unit over the scan output of the
    # nearest mamba layer before it), "cross_attention" (queries of its
    # own over the K/V of the nearest full_attention layer before it) and
    # "conv" (a gated short convolution of short_conv taps, ShortConv) ---
    # "layer": LayerNorm with scale and bias where "rms" has RMSNorm
    norm: str = "rms"
    # logits = x E^T with the embedding's own rows: one leaf, no lm_head
    tie_embeddings: bool = False
    # differential attention: heads pair up (2j, 2j+1), a pair's output is
    # (softmax(q1 k1^T) - lam softmax(q2 k2^T)) [v1, v2], RMSNorm over the
    # pair's 2 * head_dim, times 1 - lam0; lam0 = 0.8 - 0.6 exp(-0.3 i) with
    # i the layer's index in the published model (layer_indices; () = its
    # index here).  Its q, k, v and out projections have biases and it
    # rotates nothing: such a stack has no positions at all.
    diff_attn: bool = False
    layer_indices: tuple = ()
    # the Mamba mixer: state a channel, convolution taps, d_inner / d_model
    # (the rank of the step's projection is ceil(d_model / 16), ssm_rank)
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    # the gated short convolution's taps (a conv layer's causal window)
    short_conv: int = 3
    # --- latent attention (Mla, DeepSeek-V2/V3) in every attention layer
    # where kv_lora_rank > 0: q = x W_q (n_heads x (qk_nope_head_dim +
    # qk_rope_head_dim)); [c, k_pe] = x W_kva, c the kv_lora_rank-wide
    # latent behind its own RMSNorm; [k_nope, v] = c W_kvb per head;
    # the rotary part of q and the one k_pe every head shares rotate by
    # the layer's Rope; K/V heads are the query heads (head_dim and
    # n_kv_heads unused) ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    def __post_init__(self):
        if self.layer_types:
            if len(self.layer_types) != self.n_layers or set(self.layer_types) - set(LAYER_KINDS):
                raise ValueError(
                    f"layer_types must name each of the {self.n_layers} layers "
                    f"as one of {sorted(LAYER_KINDS)}, got {self.layer_types!r}"
                )
            for reader, source in _CARRIED.items():
                if reader in self.layer_types and source not in self.layer_types[
                        :self.layer_types.index(reader)]:
                    raise ValueError(
                        f"a {reader} layer reads what a {source} layer before it "
                        f"keeps; {self.layer_types!r} has none there"
                    )
            if "cross_attention" in self.layer_types and not self.diff_attn:
                raise ValueError("cross_attention layers are built as differential "
                                 "attention (diff_attn=True)")
            if "conv" in self.layer_types and self.short_conv < 1:
                raise ValueError(f"a conv layer needs short_conv >= 1 taps, "
                                 f"got {self.short_conv}")
        if self.diff_attn and (self.n_heads % 2 or self.kv_heads % 2
                               or self.qk_norm or self.attn_gate):
            raise ValueError("diff_attn pairs heads up: n_heads and n_kv_heads must "
                             "be even; it has no q/k norms and no gate")
        if self.layer_indices and len(self.layer_indices) != self.n_layers:
            raise ValueError(f"layer_indices must give each of the {self.n_layers} "
                             f"layers' published index, got {self.layer_indices!r}")
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"norm must be 'rms' or 'layer', got {self.norm!r}")
        if self.tie_embeddings and (self.ce_chunk or self.ce_vocab_chunk):
            raise ValueError("tie_embeddings with a chunked loss edge is not built: "
                             "the chunked paths read lm_head's own kernel")
        if self.moe_router not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_router must be 'softmax' or 'sigmoid', got {self.moe_router!r}"
            )
        idx, shares = self.expert_share
        if not (0 <= idx < shares) or (self.num_experts and self.num_experts % shares):
            raise ValueError(
                f"expert_share {self.expert_share!r} must be (index, shares) with "
                f"index < shares and shares dividing num_experts {self.num_experts}"
            )
        if self.moe_layer not in ("", "capacity", "dropless"):
            raise ValueError(
                f"moe_layer must be 'capacity', 'dropless' or '' (the score "
                f"function's first pairing), got {self.moe_layer!r}"
            )
        if self.moe_router == "sigmoid" and not self.moe_dropless:
            raise ValueError("sigmoid scores under a token capacity are not built: "
                             "moe_router='sigmoid' takes moe_layer='dropless'")
        if not self.moe_dropless and (
                shares > 1 or self.num_shared_experts or self.route_scale != 1.0
                or self.route_eps != 1e-20):
            raise ValueError(
                "expert_share, num_shared_experts, route_scale and route_eps are the dropless "
                "layer's (moe_layer='dropless', under either score function); "
                "the capacity layer would ignore them"
            )
        for kind, rope in self.rope_by_kind:
            if kind not in ("sliding_attention", "full_attention") or not (
                    rope is None or isinstance(rope, Rope)):
                raise ValueError(
                    f"rope_by_kind pairs 'sliding_attention' or 'full_attention' "
                    f"with a Rope or None, got {(kind, rope)!r}"
                )
        if self.rope_by_kind and self.diff_attn:
            raise ValueError("diff_attn rotates nothing: it takes no rope_by_kind")
        if self.kv_lora_rank:
            self._validate_mla()
        if self.moe_ep not in ("auto", "gspmd", "alltoall"):
            raise ValueError(
                f"moe_ep must be 'auto', 'gspmd' or 'alltoall', got "
                f"{self.moe_ep!r}"
            )
        if self.num_experts and self.capacity_factor_min <= 0:
            raise ValueError(
                f"capacity_factor_min must be > 0, got "
                f"{self.capacity_factor_min}"
            )
        if self.n_kv_heads and self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads {self.n_heads} must divide by n_kv_heads "
                f"{self.n_kv_heads} (grouped-query attention)"
            )
        if self.attn_window < 0:
            raise ValueError(
                f"attn_window must be >= 0, got {self.attn_window} "
                "(0 = full causal history)"
            )
        if self.attn_window and not self.causal:
            raise ValueError(
                "attn_window > 0 requires causal=True (sliding causal "
                "window); bidirectional encoders have no decode order to "
                "window over"
            )
        if self.ce_vocab_chunk < 0:
            raise ValueError(
                f"ce_vocab_chunk must be >= 0, got {self.ce_vocab_chunk}"
            )
        if self.ce_chunk and self.ce_vocab_chunk:
            raise ValueError(
                "ce_chunk and ce_vocab_chunk are mutually exclusive "
                "(token-chunked vs vocab-streamed loss edge)"
            )
        if self.ce_chunk < 0:
            raise ValueError(
                f"ce_chunk must be >= 0, got {self.ce_chunk} (0 = dense CE)"
            )

    def _validate_mla(self):
        widths = (self.kv_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim,
                  self.v_head_dim)
        if min(widths) <= 0 or self.qk_rope_head_dim % 2:
            raise ValueError(
                f"latent attention needs kv_lora_rank, qk_nope_head_dim, an even "
                f"qk_rope_head_dim and v_head_dim all > 0, got {widths}")
        if self.n_kv_heads not in (0, self.n_heads):
            raise ValueError("latent attention projects K and V for every query head: "
                             f"n_kv_heads must be 0 or n_heads, got {self.n_kv_heads}")
        if self.diff_attn or self.qk_norm or self.attn_gate:
            raise ValueError("latent attention has no differential heads, q/k norms "
                             "or output gate")
        if self.num_experts and not self.moe_dropless:
            raise ValueError("latent attention beside the capacity expert layer is not "
                             "built: it takes moe_layer='dropless'")
        if self.attn_impl != "dense":
            raise ValueError(f"latent attention runs on the dense and flash cores; "
                             f"attn_impl={self.attn_impl!r} (ring, Ulysses) is not built "
                             "for its Q/K wider than V")

    @property
    def mla(self) -> bool:
        """Attention layers are latent attention (``Mla``)."""
        return self.kv_lora_rank > 0

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def moe_dropless(self) -> bool:
        """Whether the expert layer computes every choice (``moe_layer``)."""
        if self.moe_layer:
            return self.moe_layer == "dropless"
        return self.moe_router == "sigmoid"

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.expert_share[1]

    @property
    def expert_width(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def layers_alike(self) -> bool:
        """Every layer is the same block: what a path that stacks one
        block's parameters for all layers (the pipeline) can run."""
        return not self.layer_types and not (self.num_experts and self.num_dense_layers)

    def layer_kind(self, i: int) -> str:
        """Layer ``i``'s mixer, one of ``LAYER_KINDS``; without a pattern
        every layer is attention, windowed iff ``attn_window``."""
        if self.layer_types:
            return self.layer_types[i]
        return "sliding_attention" if self.attn_window else "full_attention"

    def layer_window(self, i: int) -> int:
        """Layer ``i``'s attention window (0 = all of the past)."""
        if self.layer_types and self.layer_types[i] != "sliding_attention":
            return 0
        return self.attn_window

    def layer_rope(self, i: int) -> Optional[Rope]:
        """Layer ``i``'s rotary positions, None where it rotates nothing:
        its kind's entry of ``rope_by_kind``, else plain rotary at
        ``rope_theta`` in every layer of a stack without a pattern and in
        a pattern's sliding layers; never under ``diff_attn``."""
        kind = self.layer_kind(i)
        by_kind = dict(self.rope_by_kind)
        if kind in by_kind:
            return by_kind[kind]
        if self.diff_attn or (self.layer_types and kind != "sliding_attention"):
            return None
        return Rope(self.rope_theta)

    def layer_is_kept(self, i: int) -> bool:
        """Whether a later layer reads what layer ``i`` computes: the scan
        output of the last mamba layer before a gmu, the K and V of the
        last full_attention layer before a cross_attention."""
        kind = self.layer_kind(i)
        reader = {src: rd for rd, src in _CARRIED.items()}.get(kind)
        for later in self.layer_types[i + 1:] if reader else ():
            if later in (reader, kind):
                return later == reader
        return False

    def layer_lam0(self, i: int) -> float:
        index = self.layer_indices[i] if self.layer_indices else i
        return 0.8 - 0.6 * math.exp(-0.3 * index)

    @property
    def carries(self) -> bool:
        """The stack hands values from layer to layer beside ``x``."""
        return any(kind in _CARRIED for kind in self.layer_types)

    @property
    def recurrent(self) -> tuple:
        """The kinds of layer in the pattern that no decode cache holds."""
        return tuple(k for k in ("mamba", "gmu", "cross_attention", "conv")
                     if k in self.layer_types)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_rank(self) -> int:
        return -(-self.d_model // 16)

    def layer_is_moe(self, i: int) -> bool:
        return self.num_experts > 0 and i >= self.num_dense_layers


REMAT_POLICIES = ("full", "dots", "dots_no_batch")


def remat_block(cfg) -> type:
    """The Block class under this config's remat settings — the single
    construction every builder (TransformerLM, ViT, the pipeline step
    factories) must use so remat semantics cannot drift between paths.
    ``static_argnums=(3,)`` (``Block.__call__(self, x, cache,
    deterministic, carry)``) keeps ``deterministic`` a Python bool through
    the checkpoint wrapper; ``carry`` is an input like ``x``, so what
    earlier layers handed down is saved and not recomputed.  Valid policy names: ``REMAT_POLICIES`` (the
    CLIs use it for their argparse choices)."""
    if not cfg.remat:
        return Block
    policies = {
        "full": None,
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }
    assert set(policies) == set(REMAT_POLICIES)
    if cfg.remat_policy not in policies:
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r} "
            f"(expected one of {sorted(policies)})"
        )
    policy = policies[cfg.remat_policy]
    if policy is None:
        return nn.remat(Block, static_argnums=(3,))
    return nn.remat(Block, static_argnums=(3,), policy=policy)


def _rope(x, rope: Rope, positions=None):
    """Rotary embeddings by the layer's table (``LMConfig.layer_rope``).
    x: (B, T, H, D); ``positions`` overrides the
    default global positions 0..T-1 — (T,) shared across the batch
    (incremental decode passes ``offset + arange(T)``) or (B, T)
    per-row (the serving engine's continuous decode batch, where each
    lane sits at its own sequence offset)."""
    _, t, _, d = x.shape
    half = d // 2
    freqs = rope.inv_freq(half)
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., T, half)
    if angles.ndim == 2:  # shared row broadcasts over the batch
        angles = angles[None]

    def table(fn):
        y = fn(angles)
        if rope.attention_factor != 1.0:  # YaRN scales the table
            y = y * rope.attention_factor
        return y[:, :, None, :].astype(x.dtype)

    cos, sin = table(jnp.cos), table(jnp.sin)
    if rope.interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


class RMSNorm(nn.Module):
    dtype: Any = jnp.float32
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("norm",)),
            (x.shape[-1],),
            jnp.float32,
        )
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    """LayerNorm with a learned scale and bias, in float32."""

    dtype: Any = jnp.float32
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        def vec(name, init):
            return self.param(name, nn.with_logical_partitioning(init, ("norm",)),
                              (x.shape[-1],), jnp.float32)

        scale = vec("scale", nn.initializers.ones_init())
        bias = vec("bias", nn.initializers.zeros_init())
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        return ((x32 - mean) * jax.lax.rsqrt(var + self.eps) * scale + bias).astype(self.dtype)


def block_norm(cfg, name: str) -> nn.Module:
    """The configuration's norm over d_model (``LMConfig.norm``)."""
    cls = LayerNorm if cfg.norm == "layer" else RMSNorm
    return cls(cfg.dtype, cfg.norm_eps, name=name)


class QDense(nn.Module):
    """``nn.Dense(use_bias=False)`` twin that transparently supports
    weight-only int8 parameter trees.

    With a standard f32 ``kernel`` this is exactly ``nn.Dense`` (kernel
    cast to the compute dtype, one matmul).  When the supplied tree
    carries an int8 ``kernel`` plus a sibling ``scale`` (1, features)
    leaf — built by ``ops.quant.quantize_lm_params`` — it computes
    ``(x @ W8) * s``, the per-output-channel dequant, with the int8→bf16
    convert fused by XLA into the matmul operand read (the weight is
    streamed from HBM at half width; the scale multiplies the activation-
    sized output).  The param NAME and init are identical to ``nn.Dense``,
    so training checkpoints, sharding rules and the converter are
    unaffected; quantization is purely a property of the applied tree.
    """

    features: int
    dtype: Any
    kernel_init: Any
    # a float32 ``bias`` (features,) on the logical axis ``bias_axis``
    # (the kernel's output axis)
    use_bias: bool = False
    bias_axis: Any = None
    # the product's result type, where it is not the operands' (float32
    # out of bfloat16 operands: the MXU's own accumulation, kept)
    out_dtype: Any = None

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            self.kernel_init,
            (x.shape[-1], self.features),
            jnp.float32,
        )
        if self.out_dtype is None:
            y = x.astype(self.dtype) @ kernel.astype(self.dtype)
        else:
            y = jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype),
                        preferred_element_type=self.out_dtype)
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), (self.bias_axis,)),
                (self.features,), jnp.float32,
            )
            y = y + bias.astype(y.dtype)
        if self.has_variable("params", "scale"):
            # dequant in f32, matching LMHead: casting the per-channel
            # scale to bf16 first adds up to ~0.4% systematic error on
            # top of the int8 rounding, and the multiply is only
            # activation-sized
            scale = self.get_variable("params", "scale")
            y = (y.astype(jnp.float32) * scale.astype(jnp.float32)).astype(
                self.dtype
            )
        return y


def refuse_cache_over_layer_types(cfg: LMConfig) -> None:
    """The one refusal of incremental decode over a layer pattern, raised
    by ``Block`` and ``Attention`` for any cache and by the serving
    factory before it builds a program."""
    if cfg.mla:
        raise NotImplementedError(
            "this configuration trains only: its latent attention would cache "
            "one latent and one rotary key a token and decode with the K and V "
            "up-projections absorbed into the query and the output; neither "
            "that cache kind nor that decode is built (ROADMAP R3)"
        )
    if cfg.recurrent:
        raise NotImplementedError(
            f"this configuration trains only: its {', '.join(cfg.recurrent)} "
            "layers keep a recurrent state (a convolution's tail, a scan's "
            "state, a kept layer's output) that no decode cache or KV pool "
            "holds a lane of; serving it is not built (ROADMAP R4)"
        )
    if cfg.layer_types:
        raise NotImplementedError(
            "a decode cache over mixed sliding and full layers is not "
            "built: a sliding layer needs O(window) rows or blocks and a "
            "full layer all of them, and the caches and the KV pool hold "
            "one kind for every layer (ROADMAP R2)"
        )


def _layer_core(cfg: LMConfig, attn_core, window: int) -> Callable:
    """The attention core ``(q, k, v) -> o`` of a layer with ``window``:
    the dense one where none was injected; a core built for a pattern
    takes the layer's window; any other is bound to its own already."""
    if attn_core is None:
        return partial(dense_attention, causal=cfg.causal, window=window)
    if cfg.layer_types:
        return partial(attn_core, window=window)
    return attn_core


class Attention(nn.Module):
    """Causal self-attention.  Two modes share the same parameters:

    * training/eval (``cache=None``): full-sequence attention through
      ``attn_core`` (dense, ring, Ulysses, or flash).
    * incremental decode (``cache`` = a cache object, ``infer/kv_cache.py``
      or ``serve/kv_pool.PagedKV``): the new tokens are rotated at
      ``cache.positions(t)`` and ``cache.attend`` writes their K/V where
      that cache keeps them and attends what it holds; returns
      ``(out, new_cache)``.  The layer's own are the projections, the
      norms, the rotation, the gate; where K/V rows are and how they are
      indexed is the cache's.
    """

    cfg: LMConfig
    attn_core: Optional[Callable] = None
    # this layer's kind (LMConfig.layer_window / layer_rope); None = the
    # model-wide attn_window, and no rotation
    window: Optional[int] = None
    rope: Optional[Rope] = None

    @nn.compact
    def __call__(self, x, cache=None):
        cfg = self.cfg
        window = cfg.attn_window if self.window is None else self.window
        if cache is not None:
            refuse_cache_over_layer_types(cfg)
        b, t, _ = x.shape
        # kernels are flat (embed, heads*head_dim) with the fused dim sharded
        # over 'model' — identical placement to a per-head split, one matmul.
        qkv_kernel = nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ("embed", "heads")
        )

        def proj(name, heads):
            y = QDense(
                heads * cfg.head_dim,
                dtype=cfg.dtype,
                kernel_init=qkv_kernel,
                name=name,
            )(x)
            return y.reshape(b, t, heads, cfg.head_dim)

        q = proj("q", cfg.n_heads)
        k = proj("k", cfg.kv_heads)
        v = proj("v", cfg.kv_heads)
        if cfg.qk_norm:
            q = RMSNorm(cfg.dtype, cfg.norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.dtype, cfg.norm_eps, name="k_norm")(k)
        if self.rope is not None:
            positions = None if cache is None else cache.positions(t)
            q = _rope(q, self.rope, positions)
            k = _rope(k, self.rope, positions)
        spec = ("batch", "act_seq", "act_heads", None)
        q = nn.with_logical_constraint(q, spec)
        k = nn.with_logical_constraint(k, spec)
        v = nn.with_logical_constraint(v, spec)
        # every core is grouped-native (dense groups by query reshape;
        # flash indexes the shared K/V head per BlockSpec; ring
        # ppermutes and Ulysses all-to-alls Hkv-head K/V) — K/V are
        # never broadcast to H heads, so the manual cores' HBM and
        # collective traffic keep GQA's Hkv/H savings.
        core = _layer_core(cfg, self.attn_core, window)
        if cache is None:
            o = core(q, k, v)
        else:
            o, cache = cache.attend(q, k, v, window=window, core=core)
        o = nn.with_logical_constraint(o, spec)
        o = o.reshape(b, t, cfg.n_heads * cfg.head_dim)
        if cfg.attn_gate:
            g = QDense(
                cfg.n_heads * cfg.head_dim, dtype=cfg.dtype,
                kernel_init=qkv_kernel, name="gate",
            )(x)
            o = (o.astype(jnp.float32)
                 * jax.nn.sigmoid(g.astype(jnp.float32))).astype(cfg.dtype)
        out = QDense(
            cfg.d_model,
            dtype=cfg.dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("heads", "embed")
            ),
            name="out",
        )(o)
        out = nn.with_logical_constraint(out, ("batch", "act_seq", "act_embed"))
        return out if cache is None else (out, cache)


class Mla(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3), the training form:

        q = x W_q                       (n_heads, nope + rope) a token
        [c, k_pe] = x W_kva             the latent (kv_lora_rank) and one
                                        rotary key (rope) every head shares
        [k_nope, v] = RMSNorm(c) W_kvb  (n_heads, nope + v_head_dim)
        Q = [q_nope, rot(q_pe)], K = [k_nope, rot(k_pe)], V = v

    then the attention core over Q and K ``nope + rope`` wide and V
    ``v_head_dim`` wide, scaled by ``(nope + rope)^-1/2`` (the cores scale
    by Q's width), and ``W_o``.  Every projection is a ``QDense`` with no
    bias in the compute type; the rotation is the layer's ``Rope`` over
    the rotary parts only.  The latent path (``W_kva``, the latent's norm,
    ``W_kvb`` and ``k_pe`` broadcast to the heads into K) runs under the
    scope ``latent``: what an absorbed or fused latent path would take."""

    cfg: LMConfig
    attn_core: Optional[Callable] = None
    window: int = 0
    rope: Optional[Rope] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, _ = x.shape
        h, nope, rot = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        rank, dv = cfg.kv_lora_rank, cfg.v_head_dim

        def proj(features, axes, name):
            return QDense(features, dtype=cfg.dtype, name=name,
                          kernel_init=nn.with_logical_partitioning(
                              nn.initializers.lecun_normal(), axes))

        q = proj(h * (nope + rot), ("embed", "heads"), "q")(x).reshape(b, t, h, nope + rot)
        with jax.named_scope("latent"):
            ckv = proj(rank + rot, ("embed", None), "kv_a")(x)
            c = RMSNorm(cfg.dtype, cfg.norm_eps, name="kv_norm")(ckv[..., :rank])
            kv = proj(h * (nope + dv), (None, "heads"), "kv_b")(c).reshape(b, t, h, nope + dv)
        q_pe, k_pe = q[..., nope:], ckv[..., None, rank:]
        if self.rope is not None:
            q_pe, k_pe = _rope(q_pe, self.rope), _rope(k_pe, self.rope)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        with jax.named_scope("latent"):
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (b, t, h, rot))],
                                axis=-1)
        spec = ("batch", "act_seq", "act_heads", None)
        q, k, v = (nn.with_logical_constraint(a, spec) for a in (q, k, kv[..., nope:]))
        o = nn.with_logical_constraint(_layer_core(cfg, self.attn_core, self.window)(q, k, v),
                                       spec)
        out = proj(cfg.d_model, ("heads", "embed"), "out")(o.reshape(b, t, h * dv))
        return nn.with_logical_constraint(out, ("batch", "act_seq", "act_embed"))


class DiffAttention(nn.Module):
    """Differential attention over head pairs, self or cross.

    Heads pair up ``(2j, 2j+1)``: a pair's output is ``(A1 - lam A2)
    [v1, v2]`` with ``A1 = softmax(q1 k1^T / sqrt(head_dim))``, ``A2`` of
    the pair's second heads, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
    lam0`` from four learned vectors, then RMSNorm over the pair's
    ``2 * head_dim`` and a factor ``1 - lam0``.  With ``kv`` (a kept
    layer's K and V) the layer projects only its own queries.

    Both score matrices against ``V = [v1, v2]`` are one call of the
    attention core: the heads are laid out first halves then second
    halves, so grouped-query indexing puts ``q1`` on ``k1`` and ``q2`` on
    ``k2``, and V, twice as wide as Q and K, is held once under each half
    (the cores take a V head of its own width; on a v5e the flash kernels
    take 7.1 and 11.1 ms a window-512 and a full layer of the benchmark's
    cell this way, forward and backward, against 14.4 and 22.4 as two
    calls of one head size over ``[v1, v1]`` and ``[v2, v2]``: PERF.md
    section 6, PR 31).  Returns ``(out, (k, v))``.
    """

    cfg: LMConfig
    attn_core: Optional[Callable] = None
    window: int = 0
    lam0: float = 0.0
    cross: bool = False

    @nn.compact
    def __call__(self, x, kv=None):
        cfg = self.cfg
        b, t, _ = x.shape
        h, hkv, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim

        def proj(name, heads):
            y = QDense(
                heads * dh, dtype=cfg.dtype, use_bias=True, bias_axis="heads",
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "heads")),
                name=name,
            )(x)
            return y.reshape(b, t, heads, dh)

        q = proj("q", h)
        k, v = kv if self.cross else (proj("k", hkv), proj("v", hkv))

        def lam_vec(name):
            return self.param(
                name, nn.with_logical_partitioning(nn.initializers.normal(0.1), (None,)),
                (dh,), jnp.float32)

        lam = (jnp.exp(jnp.sum(lam_vec("lambda_q1") * lam_vec("lambda_k1")))
               - jnp.exp(jnp.sum(lam_vec("lambda_q2") * lam_vec("lambda_k2")))
               + self.lam0)

        def halves(a):
            # heads (2j, 2j+1) -> every first head, then every second
            n = a.shape[2] // 2
            return a.reshape(b, t, n, 2, dh).transpose(0, 1, 3, 2, 4).reshape(b, t, 2 * n, dh)

        spec = ("batch", "act_seq", "act_heads", None)
        qh = nn.with_logical_constraint(halves(q), spec)
        kh = nn.with_logical_constraint(halves(k), spec)
        # a K/V pair's [v1, v2], under the pair's k1 and again under its k2
        vv = v.reshape(b, t, hkv // 2, 2 * dh)
        vv = nn.with_logical_constraint(jnp.concatenate([vv, vv], axis=2), spec)
        # (B, T, H, 2 head_dim): every pair's A1 V, then every pair's A2 V
        o = _layer_core(cfg, self.attn_core, self.window)(qh, kh, vv).astype(jnp.float32)
        o = o[:, :, : h // 2] - lam * o[:, :, h // 2:]
        o = RMSNorm(jnp.float32, cfg.norm_eps, name="subln")(o) * (1.0 - self.lam0)
        out = QDense(
            # the bias is d_model-sized: whole on every device, as a norm's
            cfg.d_model, dtype=cfg.dtype, use_bias=True, bias_axis="norm",
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("heads", "embed")),
            name="out",
        )(o.astype(cfg.dtype).reshape(b, t, h * dh))
        out = nn.with_logical_constraint(out, ("batch", "act_seq", "act_embed"))
        return out, (k, v)


class CausalConv(nn.Module):
    """Causal depthwise convolution over time, in float32:
    ``y_t = b + sum_j w_j x_{t - (taps - 1) + j}``, ``b`` 0 and no leaf
    without ``use_bias``."""

    taps: int
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        t, c = x.shape[1], x.shape[2]
        w = self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=0,
                                                 out_axis=1), (None, "mlp")),
            (self.taps, c), jnp.float32)
        padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (self.taps - 1, 0), (0, 0)))
        y = sum(padded[:, j:j + t] * w[j] for j in range(self.taps))
        if not self.use_bias:
            return y
        bias = self.param(
            "bias", nn.with_logical_partitioning(nn.initializers.zeros_init(), ("mlp",)),
            (c,), jnp.float32)
        return bias + y


def _ssm_dense(cfg, features, axes, name, **kw):
    return QDense(
        features, dtype=cfg.dtype, name=name,
        kernel_init=nn.with_logical_partitioning(nn.initializers.lecun_normal(), axes),
        **kw,
    )


class Mamba(nn.Module):
    """The Mamba-1 mixer: ``[xs, z]`` from the input, a causal depthwise
    convolution and silu on ``xs``, the step ``dt``, ``B`` and ``C`` from
    the result, the selective scan (``ops/selective_scan.py``: float32
    state and decay), the gate ``silu(z)``, the out projection.  The
    projections' operands are in the compute type; what feeds the scan
    stays float32.  Returns ``(out, s)`` with ``s`` the scan's output
    before the gate, what a later ``Gmu`` reads."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x):
        from ddl_tpu.ops.selective_scan import selective_scan

        cfg = self.cfg
        d_in, n, r = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_rank
        wide = ("batch", "act_seq", "act_mlp")
        xs = nn.with_logical_constraint(
            _ssm_dense(cfg, d_in, ("embed", "mlp"), "in_x")(x), wide)
        z = nn.with_logical_constraint(
            _ssm_dense(cfg, d_in, ("embed", "mlp"), "in_z")(x), wide)
        xc = jax.nn.silu(CausalConv(cfg.ssm_conv, name="conv")(xs))
        proj = _ssm_dense(cfg, r + 2 * n, ("mlp", None), "x_proj",
                          out_dtype=jnp.float32)(xc)
        dt = jax.nn.softplus(_ssm_dense(
            cfg, d_in, (None, "mlp"), "dt_proj", out_dtype=jnp.float32,
            use_bias=True, bias_axis="mlp")(proj[..., :r]))
        a_log = self.param(
            "A_log",
            nn.with_logical_partitioning(
                lambda key, shape, dtype: jnp.broadcast_to(
                    jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape),
                ("mlp", None)),
            (d_in, n), jnp.float32)
        skip = self.param(
            "D", nn.with_logical_partitioning(nn.initializers.ones_init(), ("mlp",)),
            (d_in,), jnp.float32)
        with jax.named_scope("scan"):
            s, absmax = selective_scan(xc, dt, -jnp.exp(a_log), proj[..., r:r + n],
                             proj[..., r + n:], skip)
        self.sow("intermediates", "ssm_state_absmax", absmax)
        gated = (s * jax.nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)
        out = _ssm_dense(cfg, cfg.d_model, ("mlp", "embed"), "out_proj")(gated)
        out = nn.with_logical_constraint(out, ("batch", "act_seq", "act_embed"))
        return out, s.astype(cfg.dtype)


class Gmu(nn.Module):
    """Gated Memory Unit: ``W_out(m * silu(W_in x))`` with ``m`` the scan
    output an earlier Mamba layer kept, at the same positions."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x, m):
        cfg = self.cfg
        g = _ssm_dense(cfg, cfg.ssm_inner, ("embed", "mlp"), "in_proj")(x)
        g = nn.with_logical_constraint(g, ("batch", "act_seq", "act_mlp"))
        gated = (m.astype(jnp.float32) * jax.nn.silu(g.astype(jnp.float32))).astype(cfg.dtype)
        out = _ssm_dense(cfg, cfg.d_model, ("mlp", "embed"), "out_proj")(gated)
        return nn.with_logical_constraint(out, ("batch", "act_seq", "act_embed"))


class ShortConv(nn.Module):
    """The gated short convolution: ``[B, C, x] = split3(x W_in)`` (in that
    order), ``v = conv(B * x)`` causal and depthwise over ``short_conv``
    taps with no bias, ``out = (C * v) W_out``; no biases anywhere.  The
    products and the taps are float32 from the compute-type projection,
    under the scope ``short``; the out projection takes the compute type.
    Its decode state would be the last ``short_conv - 1`` positions of
    ``B * x``, per lane: ``LMConfig.recurrent``."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d = cfg.d_model
        bcx = _ssm_dense(cfg, 3 * d, ("embed", "mlp"), "in_proj")(x)
        bcx = nn.with_logical_constraint(bcx, ("batch", "act_seq", "act_mlp"))
        with jax.named_scope("short"):
            b, c, u = (bcx[..., i * d:(i + 1) * d].astype(jnp.float32) for i in range(3))
            v = CausalConv(cfg.short_conv, use_bias=False, name="conv")(b * u)
            y = (c * v).astype(cfg.dtype)
        out = _ssm_dense(cfg, d, ("mlp", "embed"), "out_proj")(y)
        return nn.with_logical_constraint(out, ("batch", "act_seq", "act_embed"))


class Mlp(nn.Module):
    cfg: LMConfig
    d_ff: int = 0  # 0 = cfg.d_ff (a shared expert passes its own width)

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg

        def up(name):
            return QDense(
                self.d_ff or cfg.d_ff,
                dtype=cfg.dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "mlp")
                ),
                name=name,
            )(x)

        h = up("wi")
        if cfg.mlp_gated:
            h = _swiglu(up("wg"), h)
        else:
            h = nn.gelu(h)
        h = nn.with_logical_constraint(h, ("batch", "act_seq", "act_mlp"))
        out = QDense(
            cfg.d_model,
            dtype=cfg.dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("mlp", "embed")
            ),
            name="wo",
        )(h)
        return nn.with_logical_constraint(out, ("batch", "act_seq", "act_embed"))


def _swiglu(gate, up):
    """``silu(gate) * up``, the product taken in float32."""
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(up.dtype)


def _top_k_dispatch(gates, k: int, capacity: int):
    """GShard-style top-k routing with per-group token capacity.

    gates: (B, S, E) router probabilities.  Returns (dispatch, combine),
    both (B, S, E, C): dispatch is a 0/1 routing tensor, combine carries the
    (renormalised) gate weights.  Tokens claim expert slots in priority
    order (choice rank, then position); overflow tokens are dropped —
    uniform static shapes, no data-dependent control flow.
    """
    b, s, e = gates.shape
    g = gates
    dispatch = jnp.zeros((b, s, e, capacity), gates.dtype)
    combine = jnp.zeros((b, s, e, capacity), gates.dtype)
    counts = jnp.zeros((b, e), gates.dtype)
    selected_mass = jnp.zeros((b, s), gates.dtype)
    for _ in range(k):
        idx = jnp.argmax(g, axis=-1)  # (B, S)
        onehot = jax.nn.one_hot(idx, e, dtype=gates.dtype)
        gate_j = (g * onehot).sum(-1)  # (B, S)
        pos = jnp.cumsum(onehot, axis=1) - 1 + counts[:, None, :]  # (B, S, E)
        counts = counts + onehot.sum(axis=1)
        pos_tok = (pos * onehot).sum(-1)  # (B, S)
        keep = (pos_tok < capacity).astype(gates.dtype)
        pos_oh = jax.nn.one_hot(pos_tok.astype(jnp.int32), capacity, dtype=gates.dtype)
        d = onehot[..., None] * pos_oh[:, :, None, :] * keep[..., None, None]
        dispatch = dispatch + d
        combine = combine + d * gate_j[..., None, None]
        selected_mass = selected_mass + gate_j * keep
        g = g * (1.0 - onehot)
    combine = combine / jnp.maximum(selected_mass, 1e-9)[..., None, None]
    return dispatch, combine


def moe_routing_plan(cfg, seq_len: int) -> tuple[str, int]:
    """The (dispatch_impl, group_size) a MoE layer actually uses at this
    sequence length — shared by ``MoeMlp`` and the bench so reported
    configs can't drift from executed ones.

    The group is the largest divisor of ``seq_len`` at or under
    ``cfg.moe_group``; when no usable divisor exists (e.g. prime or
    near-prime lengths would collapse to 1-2 token groups, destroying
    routing/load-balance quality), the whole sequence routes as one group
    instead.  ``moe_dispatch="auto"`` resolves by the measured crossover
    (PERF.md MoE table): one-hot einsum matmuls up to 2048-token groups,
    argsort + permutation gathers beyond."""
    g = min(cfg.moe_group, seq_len) if cfg.moe_group else seq_len
    while seq_len % g:
        g -= 1
    if cfg.moe_group and g < min(cfg.moe_group, seq_len) / 2:
        g = seq_len
    impl = cfg.moe_dispatch
    if impl == "auto":
        impl = "einsum" if g <= 2048 else "sort"
    if impl not in ("sort", "einsum"):
        raise ValueError(
            f"moe_dispatch must be 'auto', 'sort' or 'einsum', got "
            f"{cfg.moe_dispatch!r}"
        )
    return impl, g


def _sort_dispatch(gates, k: int, capacity: int):
    """Sort-based top-k routing — same slot assignment as
    ``_top_k_dispatch`` without the (B, S, E, C) one-hot tensors.

    Token-choices are flattened choice-rank-major (all first choices, then
    all second choices) and stably argsorted by expert id, which reproduces
    the einsum path's priority order exactly: slots fill by choice rank,
    then sequence position.  Returns index/mask arrays for a gather-based
    dispatch and combine:

    - ``slot_token`` (B, E*C) int32: source token for each expert slot
    - ``slot_valid`` (B, E*C): 1.0 where the slot is filled
    - ``slot_choice`` (B, E*C) int32: flat (k-major) choice index that
      fills each slot (the combine gather's inverse, used by its VJP)
    - ``choice_slot`` (B, K, S) int32: destination slot per token-choice
      (clamped; dropped choices carry weight 0)
    - ``choice_keep`` (B, K, S) bool: which choices found a slot
    - ``choice_weight`` (B, K, S): renormalised gate weight, 0 if dropped
    - ``frac`` (E,): kept token-choices per token, per expert (the einsum
      path's ``dispatch.sum(-1).mean((0, 1))``)
    - ``kept`` (): fraction of all token-choices that found a slot
    """
    b, s, e = gates.shape
    n = k * s
    gate_vals, expert_idx = jax.lax.top_k(gates, k)  # (B, S, K)
    expert_flat = expert_idx.transpose(0, 2, 1).reshape(b, n)  # k-major
    sort_ord = jnp.argsort(expert_flat, axis=-1, stable=True)  # (B, N)
    sorted_expert = jnp.take_along_axis(expert_flat, sort_ord, axis=-1)
    # position inside each expert's run = sorted index - group start
    counts = (expert_flat[..., None] == jnp.arange(e)).sum(1)  # (B, E)
    starts = jnp.cumsum(counts, axis=-1) - counts  # exclusive
    pos_in_e = jnp.arange(n)[None, :] - jnp.take_along_axis(
        starts, sorted_expert, axis=-1
    )
    keep_sorted = pos_in_e < capacity
    # overflow choices target slot E*C: out of bounds, so the scatter's
    # mode='drop' discards them — static shapes, no branching
    slot_sorted = jnp.where(
        keep_sorted, sorted_expert * capacity + pos_in_e, e * capacity
    )
    token_sorted = sort_ord % s  # k-major flatten: flat = k_idx * s + pos
    batch_ix = jnp.arange(b)[:, None]
    slot_token = jnp.zeros((b, e * capacity), jnp.int32).at[
        batch_ix, slot_sorted
    ].set(token_sorted.astype(jnp.int32), mode="drop")
    slot_valid = jnp.zeros((b, e * capacity), gates.dtype).at[
        batch_ix, slot_sorted
    ].set(1.0, mode="drop")
    slot_choice = jnp.zeros((b, e * capacity), jnp.int32).at[
        batch_ix, slot_sorted
    ].set(sort_ord.astype(jnp.int32), mode="drop")
    # back to original choice order for the combine side
    inv = jnp.argsort(sort_ord, axis=-1)  # inverse permutation
    choice_slot = jnp.take_along_axis(slot_sorted, inv, axis=-1)
    choice_keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    gate_r = gate_vals.transpose(0, 2, 1)  # (B, K, S)
    keep_r = choice_keep.reshape(b, k, s).astype(gates.dtype)
    mass = (gate_r * keep_r).sum(1)  # (B, S)
    choice_weight = gate_r * keep_r / jnp.maximum(mass, 1e-9)[:, None, :]
    frac = (
        (expert_flat[..., None] == jnp.arange(e))
        * choice_keep[..., None]
    ).sum((0, 1)).astype(gates.dtype) / (b * s)
    kept = choice_keep.mean(dtype=gates.dtype)
    choice_slot = jnp.minimum(choice_slot, e * capacity - 1).reshape(b, k, s)
    return (slot_token, slot_valid, slot_choice, choice_slot,
            choice_keep.reshape(b, k, s), choice_weight, frac, kept)


@jax.custom_vjp
def _dispatch_gather(x, slot_token, slot_valid, choice_slot, choice_keep):
    """xe[b, slot] = x[b, slot_token[b, slot]] * valid — the dispatch data
    movement as a permutation gather.  The VJP is ALSO a gather: token t's
    gradient is the (masked) sum over its k choice slots, read back
    through ``choice_slot`` — a TPU scatter-add never appears in either
    direction (the naive ``take_along_axis`` backward is a scatter-add,
    measured ~2x the whole einsum path's cost on v5e; PERF.md MoE table)."""
    xe = jnp.take_along_axis(x, slot_token[..., None], axis=1)
    return xe * slot_valid[..., None].astype(x.dtype)


def _dispatch_gather_fwd(x, st, sv, cs, ck):
    return _dispatch_gather(x, st, sv, cs, ck), (sv, cs, ck)


def _dispatch_gather_bwd(res, g):
    sv, cs, ck = res
    b, k, s = cs.shape
    g = g * sv[..., None].astype(g.dtype)
    contrib = jnp.take_along_axis(
        g, cs.reshape(b, k * s)[..., None], axis=1
    ).reshape(b, k, s, g.shape[-1])
    dx = (contrib * ck[..., None].astype(g.dtype)).sum(axis=1)
    return dx, None, None, None, None


_dispatch_gather.defvjp(_dispatch_gather_fwd, _dispatch_gather_bwd)


@jax.custom_vjp
def _combine_gather(ye, choice_slot, slot_choice, slot_valid):
    """yc[b, choice] = ye[b, choice_slot[b, choice]] — each token-choice
    reads its expert-slot output.  Slot↔kept-choice is a bijection, so
    the VJP gathers through the inverse map ``slot_choice`` (masked by
    slot validity) instead of scatter-adding."""
    b, k, s = choice_slot.shape
    yc = jnp.take_along_axis(
        ye, choice_slot.reshape(b, k * s)[..., None], axis=1
    )
    return yc.reshape(b, k, s, ye.shape[-1])


def _combine_gather_fwd(ye, cs, sc, sv):
    return _combine_gather(ye, cs, sc, sv), (sc, sv)


def _combine_gather_bwd(res, g):
    sc, sv = res
    b = g.shape[0]
    gf = g.reshape(b, -1, g.shape[-1])
    d_ye = jnp.take_along_axis(gf, sc[..., None], axis=1)
    return d_ye * sv[..., None].astype(g.dtype), None, None, None


_combine_gather.defvjp(_combine_gather_fwd, _combine_gather_bwd)


def dropless_plan(expert_idx, lo: int, held: int, tile: int):
    """Where every token-choice of a dropless layer goes, for the experts
    ``[lo, lo + held)`` this program holds.

    ``expert_idx`` (N, K) int32, each token's chosen experts over the
    whole router.  The choices that land on held experts are sorted by
    expert into a buffer whose runs start on row-tile boundaries
    (``ops/grouped_matmul.align_groups``) and which is sized for the
    worst routing, so nothing can be dropped.  Returns a dict of index
    arrays: ``row_choice`` (R,) the flat choice (token * K + k) in each
    buffer row and ``row_valid`` (R,) whether one is; ``choice_row``
    (N, K) each choice's row and ``held`` (N, K) whether it has one;
    ``counts`` (held,) rows an expert; the grouped product's
    ``tile_group``, ``tile_src``, ``n_active``; ``pairs``, what the row
    kernels walk (``ops/moe_rows.pair_plan``)."""
    from ddl_tpu.ops.grouped_matmul import align_groups, buffer_rows
    from ddl_tpu.ops.moe_rows import pair_plan

    n, k = expert_idx.shape
    rows = buffer_rows(n * min(k, held), held, tile)
    local = expert_idx.reshape(-1) - lo
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held)  # elsewhere: sorted to the end
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = (key[:, None] == jnp.arange(held)).sum(0, dtype=jnp.int32)
    start, tile_group, tile_src, n_active = align_groups(counts, rows // tile, tile)
    packed = jnp.cumsum(counts) - counts  # a run's start in sorted order
    # a tile's group places its rows: worked out a tile and spread over the
    # tile's rows, and the permutation inverted by a sort (a gather or a
    # scatter of 65,536 scalars costs the chip 0.3-0.55 ms, the sort 0.05;
    # PERF.md section 6, PR 28)
    t = jnp.arange(rows // tile, dtype=jnp.int32)
    pos = (t * tile - start[tile_group])[:, None] + jnp.arange(tile, dtype=jnp.int32)
    row_valid = (
        (pos < counts[tile_group][:, None]) & (t < n_active[0])[:, None]
    ).reshape(rows)
    at = (packed[tile_group][:, None] + pos).reshape(rows)
    row_choice = jnp.where(row_valid, order[jnp.minimum(at, n * k - 1)], 0)
    rank = jnp.argsort(order).astype(jnp.int32)
    kc = jnp.minimum(key, held - 1)
    choice_row = jnp.where(is_held, start[kc] + rank - packed[kc], 0)
    return {
        "row_choice": row_choice, "row_valid": row_valid,
        "choice_row": choice_row.reshape(n, k), "held": is_held.reshape(n, k),
        "counts": counts, "tile_group": tile_group, "tile_src": tile_src,
        "n_active": n_active,
        "pairs": pair_plan(
            row_choice // k, row_valid, n_active, tokens=n, groups=held, tile=tile
        ),
    }


@jax.custom_vjp
def _rows_gather(x, plan):
    """``xs[r] = x[token of r]`` where the buffer row holds a choice, 0
    where it pads a run in an active tile; rows of other tiles stay
    unwritten.  The VJP sums a token's held choices' rows.  Both walk the
    plan's pairs and no further (``ops/moe_rows``)."""
    from ddl_tpu.ops.moe_rows import rows_gather

    return rows_gather(x, plan["pairs"], groups=plan["counts"].shape[0])


def _rows_gather_fwd(x, plan):
    return _rows_gather(x, plan), plan


def _rows_gather_bwd(plan, g):
    from ddl_tpu.ops.moe_rows import rows_combine

    dx = rows_combine(
        g, plan["pairs"], tokens=plan["held"].shape[0],
        groups=plan["counts"].shape[0], out_dtype=g.dtype,
        name="moe_rows_gather_bwd",
    )
    return dx, None


_rows_gather.defvjp(_rows_gather_fwd, _rows_gather_bwd)


def _row_weights(w, plan):
    """Each buffer row's routing weight, laid out as the row kernels read
    it: (row tiles, 1, tile) float32, 0 where the row holds no choice."""
    picked = jnp.take(w.reshape(-1).astype(jnp.float32), plan["row_choice"])
    return jnp.where(plan["row_valid"], picked, 0.0).reshape(
        plan["pairs"]["tok"].shape
    )


@jax.custom_vjp
def _rows_combine(o, w, plan, shared=None):
    """``y[t] = sum_k w[t, k] * o[choice_row[t, k]]`` over the held
    choices, plus ``shared[t]`` (the shared experts' output, (tokens, D))
    where there is one, all in float32 and cast to ``o``'s type once: the
    result leaves in the compute type, so its cotangent arrives in it.
    ``o``'s gradient is a gather through the inverse map, 0 on rows that
    hold no choice; ``w``'s is each held choice's row of ``o`` against the
    token's cotangent; ``shared``'s is the cotangent itself."""
    from ddl_tpu.ops.moe_rows import rows_combine

    return rows_combine(
        o, plan["pairs"], tokens=w.shape[0], groups=plan["counts"].shape[0],
        out_dtype=o.dtype, weights=_row_weights(w, plan), add=shared,
    )


def _rows_combine_fwd(o, w, plan, shared):
    # ``shared`` rides along for its presence only: the backward reads no
    # value of it, so a compiled step keeps nothing alive for it
    return _rows_combine(o, w, plan, shared), (o, w, plan, shared)


def _rows_combine_bwd(res, g):
    from ddl_tpu.ops.moe_rows import rows_gather

    o, w, plan, shared = res
    do, dots = rows_gather(
        g, plan["pairs"], groups=plan["counts"].shape[0], out_dtype=o.dtype,
        scale=_row_weights(w, plan), dot_with=o, name="moe_rows_combine_bwd",
    )
    dw = jnp.where(plan["held"], jnp.take(dots.reshape(-1), plan["choice_row"]), 0.0)
    return do, dw.astype(w.dtype), None, None if shared is None else g


_rows_combine.defvjp(_rows_combine_fwd, _rows_combine_bwd)


def _ambient_mesh_shape() -> dict:
    """Axis-name -> size of the ambient (abstract) mesh; {} when tracing
    without a mesh context (jax answers with an empty mesh, it does not
    raise).  Shared by the decode caches' mesh size
    (``infer/kv_cache.py``) and the MoE-dispatch resolution below."""
    return dict(jax.sharding.get_abstract_mesh().shape)


def _expert_axis_size() -> int:
    """Size of the ``expert`` mesh axis in the ambient (abstract) mesh —
    1 when tracing without a mesh context (plain CPU tests, decode on a
    single device), which routes MoE to the GSPMD dispatch."""
    return int(_ambient_mesh_shape().get("expert", 1))


def _ep_alltoall_moe(x, gates, wi, wo, *, top_k, capacity, ep, dt):
    """Manual expert-parallel MoE FFN: the GShard/Switch production path.

    A partial-manual ``shard_map`` over the ``expert`` mesh axis (the same
    construction as the pipeline's manual-over-``pipe`` region,
    ``parallel/lm_pipeline.py``; ``data``/``seq``/``model`` stay under
    GSPMD).  Each expert shard, holding ``B/ep`` token rows and ``E/ep``
    experts:

    1. routes its local tokens with the sort dispatch (argsort + gather,
       custom-VJP — identical slot assignment to the einsum path),
    2. ``lax.all_to_all``s the (ep, B_loc, E_loc*C, D) capacity slots so
       every slot lands on its expert's shard — ONE fused exchange where
       the GSPMD path's resharding may lower to all-gather+slice,
    3. runs the local experts' FFN with the source-shard dim as an extra
       einsum batch axis (no resharding of the received block), and
    4. reverses the exchange and combines locally (weighted gather).

    ``frac``/``kept`` routing stats are pmean'd over the axis, so the aux
    loss and router metrics match the GSPMD path exactly (parity:
    tests/test_transformer.py).  x: (B, S, D) batch-sharded over
    (data, expert); gates (B, S, E) f32; wi/wo (E, D, F)/(E, F, D)
    expert-sharded.  Returns (y, frac, kept).
    """
    from jax.sharding import PartitionSpec as P

    e = gates.shape[-1]
    e_loc = e // ep

    def body(x_l, gates_l, wi_l, wo_l):
        bl, _, d = x_l.shape
        (slot_token, slot_valid, slot_choice, choice_slot, choice_keep,
         choice_weight, frac, kept) = _sort_dispatch(gates_l, top_k, capacity)
        xe = _dispatch_gather(
            x_l, slot_token, slot_valid, choice_slot, choice_keep
        )  # (B_loc, E*C, D), expert-major slots
        send = xe.reshape(bl, ep, e_loc * capacity, d).transpose(1, 0, 2, 3)
        recv = jax.lax.all_to_all(send, "expert", 0, 0, tiled=True)
        # recv[j] = shard j's slots for MY experts -> (E_loc, ep, B_loc, C, D)
        he = recv.reshape(ep, bl, e_loc, capacity, d).transpose(2, 0, 1, 3, 4)
        h = nn.gelu(jnp.einsum("eabcd,edf->eabcf", he, wi_l.astype(dt)))
        ye = jnp.einsum("eabcf,efd->eabcd", h, wo_l.astype(dt))
        back = ye.transpose(1, 2, 0, 3, 4).reshape(ep, bl, e_loc * capacity, d)
        ret = jax.lax.all_to_all(back, "expert", 0, 0, tiled=True)
        # ret[j] = my tokens' results from shard j's experts -> global
        # expert-major slot order again
        ye_flat = ret.transpose(1, 0, 2, 3).reshape(bl, e * capacity, d)
        yc = _combine_gather(ye_flat, choice_slot, slot_choice, slot_valid)
        y = (yc * choice_weight[..., None].astype(dt)).sum(axis=1)
        return (
            y,
            jax.lax.pmean(frac, "expert"),
            jax.lax.pmean(kept, "expert"),
        )

    sm = jax.shard_map(
        body,
        in_specs=(P("expert"), P("expert"), P("expert"), P("expert")),
        out_specs=(P("expert"), P(), P()),
        axis_names={"expert"},
        check_vma=False,
    )
    return sm(x, gates, wi, wo)


class MoeMlp(nn.Module):
    """Top-k mixture-of-experts MLP with expert parallelism.

    Experts live sharded over the ``expert`` mesh axis (and their hidden dim
    over ``model`` — EP x TP); tokens are batch-sharded over ``data``.  The
    dispatch/combine einsums change an array's sharded dimension from
    token-sharded to expert-sharded, so XLA's partitioner lowers them to the
    all-to-all exchanges that GShard/Switch implement by hand.
    """

    cfg: LMConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        if cfg.moe_dropless:
            return self._dropless(x), jnp.zeros((), jnp.float32)
        b0, s0, d = x.shape
        # split the sequence into routing groups (moe_routing_plan):
        # capacity is per group and dispatch cost is O(group) per token,
        # so groups make the einsum path cheap; the group dim folds into
        # batch, which keeps data sharding intact
        dispatch_impl, g = moe_routing_plan(cfg, s0)
        n_groups = s0 // g
        if n_groups > 1:
            x = x.reshape(b0 * n_groups, g, d)
        b, s = x.shape[:2]
        e = cfg.num_experts
        capacity = max(
            1, int(cfg.expert_top_k * s * cfg.capacity_factor / e)
        )
        # router in f32 for a stable softmax/argsort
        router_logits = nn.Dense(
            e,
            use_bias=False,
            dtype=jnp.float32,
            param_dtype=jnp.float32,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "expert")
            ),
            name="router",
        )(x.astype(jnp.float32))
        gates = jax.nn.softmax(router_logits, axis=-1)  # (B, S, E)

        wi = self.param(
            "wi",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(batch_axis=(0,)),
                ("expert", "embed", "mlp"),
            ),
            (e, d, cfg.d_ff),
            jnp.float32,
        )
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(batch_axis=(0,)),
                ("expert", "mlp", "embed"),
            ),
            (e, cfg.d_ff, d),
            jnp.float32,
        )
        dt = cfg.dtype

        # manual expert-parallel exchange (moe_ep='alltoall', or 'auto'
        # with an expert mesh axis): per-shard sort dispatch + explicit
        # lax.all_to_all of the capacity slots; int8 expert banks stay on
        # the GSPMD path (the scales would have to thread the manual
        # region, and int8 serving meshes are expert=1)
        ep = _expert_axis_size() if cfg.moe_ep != "gspmd" else 1
        use_a2a = (
            ep > 1
            and e % ep == 0
            and not self.has_variable("params", "wi_scale")
        )
        if cfg.moe_ep == "alltoall" and not use_a2a:
            # explicit request unfulfillable at this trace (single-device
            # decode/eval of an alltoall-trained config is legitimate —
            # warn with the ACTUAL failed guard, don't break it)
            import warnings

            if ep <= 1:
                why = ("no expert mesh axis (>1) is visible at trace "
                       f"time (expert axis size {ep})")
            elif e % ep:
                why = f"num_experts {e} does not divide by the {ep}-way axis"
            else:
                why = ("the tree carries int8 expert scales, which the "
                       "manual exchange does not thread")
            warnings.warn(
                f"moe_ep='alltoall' requested but {why}; falling back "
                "to the GSPMD dispatch",
                stacklevel=2,
            )
        if use_a2a:
            y, frac, kept = _ep_alltoall_moe(
                x.astype(dt), gates, wi, wo,
                top_k=cfg.expert_top_k, capacity=capacity, ep=ep, dt=dt,
            )
        elif dispatch_impl == "sort":
            (slot_token, slot_valid, slot_choice, choice_slot, choice_keep,
             choice_weight, frac, kept) = _sort_dispatch(
                gates, cfg.expert_top_k, capacity
            )
        else:
            dispatch, combine = _top_k_dispatch(
                gates, cfg.expert_top_k, capacity
            )
            frac = dispatch.sum(-1).mean(axis=(0, 1))  # (E,) kept fraction
            kept = dispatch.sum() / (b * s * cfg.expert_top_k)

        # Switch-transformer load-balance loss: E * sum_e f_e * p_e where
        # f_e = fraction of tokens whose slot-0 choice is e, p_e = mean gate.
        mean_gate = gates.mean(axis=(0, 1))
        aux_loss = e * jnp.sum(frac / cfg.expert_top_k * mean_gate)

        # Router observability (sown per block; the step aggregates into
        # metrics): capacity overflow silently drops tokens, so a run must
        # be able to SEE the drop fraction and the expert load spread, not
        # just the aux loss.
        self.sow("intermediates", "moe_drop_frac", 1.0 - kept)
        # per-expert share of the kept token-choices (uniform = 1/E)
        load = frac / jnp.maximum(frac.sum(), 1e-9)
        self.sow("intermediates", "moe_expert_load", load)

        if not use_a2a:
            if dispatch_impl == "sort":
                # dispatch = batch-local permutation gather of each slot's
                # source token (custom-VJP: backward is gathers too), then
                # the same expert-sharded layout as the einsum path so the
                # act_expert constraint induces the identical all-to-all
                # under EP
                xe = _dispatch_gather(
                    x.astype(dt), slot_token, slot_valid, choice_slot,
                    choice_keep,
                )  # (B, E*C, D)
                xe = xe.reshape(b, e, capacity, d).transpose(1, 0, 2, 3)
            else:
                xe = jnp.einsum(
                    "bsec,bsd->ebcd", dispatch.astype(dt), x.astype(dt)
                )
            xe = nn.with_logical_constraint(
                xe, ("act_expert", "moe_batch", None, "act_embed")
            )
            # weight-only int8 expert banks (ops.quant.quantize_lm_params):
            # per-(expert, out-channel) scales dequant the einsum outputs
            h = jnp.einsum("ebcd,edf->ebcf", xe, wi.astype(dt))
            if self.has_variable("params", "wi_scale"):
                # (E, 1, F) -> (E, 1, 1, F) against (E, B, C, F)
                h = h * self.get_variable("params", "wi_scale")[:, None].astype(dt)
            h = nn.gelu(h)
            h = nn.with_logical_constraint(
                h, ("act_expert", "moe_batch", None, "act_mlp")
            )
            ye = jnp.einsum("ebcf,efd->ebcd", h, wo.astype(dt))
            if self.has_variable("params", "wo_scale"):
                ye = ye * self.get_variable("params", "wo_scale")[:, None].astype(dt)
            ye = nn.with_logical_constraint(
                ye, ("act_expert", "moe_batch", None, "act_embed")
            )
            if dispatch_impl == "sort":
                # combine = gather each token-choice's slot output, weight
                # by the renormalised gate, sum over the K choices
                ye_flat = ye.transpose(1, 0, 2, 3).reshape(b, e * capacity, d)
                yc = _combine_gather(ye_flat, choice_slot, slot_choice,
                                     slot_valid)
                y = (yc * choice_weight[..., None].astype(dt)).sum(axis=1)
            else:
                y = jnp.einsum("bsec,ebcd->bsd", combine.astype(dt), ye)
        if n_groups > 1:
            y = y.reshape(b0, s0, d)
        y = nn.with_logical_constraint(y, ("batch", "act_seq", "act_embed"))
        return y, aux_loss

    def _dropless(self, x):
        """The dropless layer (``moe_layer='dropless'``): scores over
        all ``num_experts`` (``moe_router``: a softmax, or sigmoids with a
        selection bias that is not trained), the top ``expert_top_k`` of
        them, the chosen scores normalised and scaled, every choice on an
        expert held here computed, the shared experts (if any) beside
        them.  Router in float32; the experts' products in the compute
        type with float32 accumulation."""
        # imported here: Pallas loads only where a dropless layer is built
        from ddl_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul, grouped_swiglu

        cfg = self.cfg
        b, t, d = x.shape
        e, k, f, dt = cfg.num_experts, cfg.expert_top_k, cfg.expert_width, cfg.dtype
        held = cfg.experts_held
        lo = cfg.expert_share[0] * held
        flat = x.reshape(b * t, d)
        with jax.named_scope("moe/route"):
            logits = nn.Dense(
                e, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "expert")
                ),
                name="router",
            )(flat.astype(jnp.float32))
            if cfg.moe_router == "sigmoid":
                scores = jax.nn.sigmoid(logits)
                # the bias a training recipe moves to balance load: not
                # trained by the loss, part of the selection only
                bias = jax.lax.stop_gradient(self.param(
                    "bias",
                    nn.with_logical_partitioning(nn.initializers.zeros_init(), (None,)),
                    (e,), jnp.float32,
                ))
                _, idx = jax.lax.top_k(scores + bias, k)
                picked = jnp.take_along_axis(scores, idx, axis=-1)
            else:
                scores = jax.nn.softmax(logits, axis=-1)
                picked, idx = jax.lax.top_k(scores, k)
                # the share of the softmax's mass the chosen hold before
                # they are normalised: k / E under uniform scores, toward
                # 1 as the router sharpens (it collapses before the rows do)
                self.sow("intermediates", "moe_topk_mass", picked.sum(-1).mean())
            weights = cfg.route_scale * picked / (
                picked.sum(-1, keepdims=True) + cfg.route_eps
            )

        def bank(name, shape, axes):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(batch_axis=(0,)), axes
                ),
                shape, jnp.float32,
            )

        wg = bank("wg", (held, d, f), ("expert", "embed", "mlp"))
        wi = bank("wi", (held, d, f), ("expert", "embed", "mlp"))
        wo = bank("wo", (held, f, d), ("expert", "mlp", "embed"))
        with jax.named_scope("moe/dispatch"):
            plan = dropless_plan(idx.astype(jnp.int32), lo, held, ROW_TILE)
            xs = _rows_gather(flat.astype(dt), plan)
        tiles = (plan["tile_group"], plan["tile_src"], plan["n_active"])
        with jax.named_scope("moe/experts"):
            h = grouped_swiglu(xs, wg, wi, *tiles)
            o = grouped_matmul(h, wo, *tiles)
        shared = None
        with jax.named_scope("moe/shared"):
            if cfg.num_shared_experts:
                shared = Mlp(cfg, f * cfg.num_shared_experts, name="shared")(
                    x.astype(dt)
                ).reshape(b * t, d)
        with jax.named_scope("moe/combine"):
            y = _rows_combine(o, weights, plan, shared)
        counts = plan["counts"]
        rows = counts.sum()
        self.sow("intermediates", "moe_local_rows", rows.astype(jnp.float32))
        # row tiles in use over the buffer's: the share of the worst case
        # that the row kernels and the grouped products walk
        self.sow(
            "intermediates", "moe_buffer_fill",
            plan["n_active"][0].astype(jnp.float32) / plan["tile_group"].shape[0],
        )
        self.sow(
            "intermediates", "moe_load_max_over_mean",
            counts.max() * held / jnp.maximum(rows, 1).astype(jnp.float32),
        )
        # what the buffer holds against what was routed here: 0 by the
        # buffer's size, counted so that a run can say so
        self.sow(
            "intermediates", "moe_rows_dropped",
            (rows - plan["row_valid"].sum()).astype(jnp.float32),
        )
        return nn.with_logical_constraint(
            y.reshape(b, t, d), ("batch", "act_seq", "act_embed")
        )


class Block(nn.Module):
    """Pre-norm decoder block: ``x + mixer(norm(x))``, then ``x +
    mlp(norm(x))``.  The mixer is the layer's kind (``LMConfig.layer_kind``):
    attention (``Attention``, ``Mla`` under ``kv_lora_rank``, or
    ``DiffAttention`` under ``diff_attn``), a
    ``Mamba`` layer, a ``Gmu``, cross-attention or a ``ShortConv``.  With ``cache``
    (incremental decode) the return gains the updated cache: ``(x, aux,
    new_cache)``.  With ``carry`` (a stack in which layers read what
    earlier ones kept, ``LMConfig.carries``: a dict, empty before the
    first kept layer) it gains the carry, with this layer's scan output
    under ``"ssm"`` or its K and V under ``"kv"`` where a later layer
    reads them: ``(x, aux, carry)``."""

    cfg: LMConfig
    attn_core: Optional[Callable] = None
    # which layer of the model this is: its kind (the mixer, window or
    # full, rotary or none, dense or expert MLP) is the configuration's,
    # LMConfig.layer_*
    layer: int = 0

    @nn.compact
    def __call__(self, x, cache=None, deterministic=True, carry=None):
        cfg = self.cfg
        drop = nn.Dropout(cfg.dropout_rate, deterministic=deterministic)
        kind, kept = cfg.layer_kind(self.layer), cfg.layer_is_kept(self.layer)
        if cache is not None:
            refuse_cache_over_layer_types(cfg)

        def norm(name):
            return block_norm(cfg, name)

        def post(name, y):
            # the residual branch's own norm, where a block has four
            return norm(name)(y) if cfg.sandwich_norm else y

        h = norm("norm_attn")(x)
        if kind == "mamba":
            a, s = Mamba(cfg, name="ssm")(h)
            if kept:
                carry = dict(carry, ssm=s)
        elif kind == "gmu":
            a = Gmu(cfg, name="gmu")(h, carry["ssm"])
        elif kind == "conv":
            a = ShortConv(cfg, name="conv")(h)
        elif cfg.mla:
            a = Mla(cfg, self.attn_core, cfg.layer_window(self.layer),
                    cfg.layer_rope(self.layer), name="mla")(h)
        elif cfg.diff_attn:
            cross = kind == "cross_attention"
            a, kv = DiffAttention(
                cfg, self.attn_core, cfg.layer_window(self.layer),
                cfg.layer_lam0(self.layer), cross, name="xattn" if cross else "attn",
            )(h, carry["kv"] if cross else None)
            if kept:
                carry = dict(carry, kv=kv)
        else:
            attn = Attention(
                cfg, self.attn_core, cfg.layer_window(self.layer),
                cfg.layer_rope(self.layer), name="attn",
            )
            if cache is None:
                a = attn(h)
            else:
                a, cache = attn(h, cache)
        x = x + drop(post("norm_post_attn", a))
        h = norm("norm_mlp")(x)
        if cfg.layer_is_moe(self.layer):
            y, aux = MoeMlp(cfg, name="moe")(h)
        else:
            y, aux = Mlp(cfg, name="mlp")(h), jnp.zeros((), jnp.float32)
        x = x + drop(post("norm_post_mlp", y))
        if carry is not None:
            return x, aux, carry
        return (x, aux) if cache is None else (x, aux, cache)


class TokenEmbed(nn.Module):
    """Token embedding with an explicit ZeRO-style lookup.

    Same param tree as ``nn.Embed`` (``embed/embedding``), but the (possibly
    FSDP/TP-sharded) table is constrained to *replicated* right before the
    gather: XLA then inserts one small all-gather of the (V, D) table and the
    gather itself stays fully local, with its output sharded by the token
    sharding.  Without this, GSPMD cannot repartition a gather whose operand
    is sharded on the offset dim and falls back to involuntary full
    rematerialization of the (B, T, D) output every step
    (``spmd_partitioner.cc:652`` warnings on fsdp pipeline meshes — a silent
    multi-chip perf tax on the LM input edge)."""

    cfg: LMConfig

    def setup(self):
        cfg = self.cfg
        self.embedding = self.param(
            "embedding",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.d_model),
            jnp.float32,
        )

    def __call__(self, tokens):
        cfg = self.cfg
        table = nn.with_logical_constraint(self.embedding, (None, None))
        x = jnp.take(table, tokens, axis=0)
        if cfg.embed_scale:
            x = x * jnp.sqrt(jnp.float32(cfg.d_model))
        return x.astype(cfg.dtype)

    def attend(self, x):
        """The tied head (``LMConfig.tie_embeddings``): float32 logits of
        ``x`` against the table's own rows, as ``LMHead`` against its
        kernel; the head's gradient adds into the embedding's."""
        return jnp.einsum("...d,vd->...v", x, self.embedding)


def make_embed(cfg: LMConfig) -> TokenEmbed:
    """The token embedding ('embed' in the param tree) — single source of
    truth shared by ``TransformerLM`` and the pipeline's stage-0 prologue
    (``parallel/lm_pipeline.py``), so full-model and pipelined param trees
    restructure 1:1."""
    return TokenEmbed(cfg, name="embed")


class LMHead(nn.Module):
    """The vocab projection ('lm_head'); f32 so loss-side softmax is f32.

    The kernel is stored (vocab, d_model) — the embedding table's
    orientation, NOT ``nn.Dense``'s (d_model, vocab).  Measured on chip
    (profile_lm, PERF.md round 4): with the Dense orientation the head
    kernel's gradient reaches the Adam fusion transposed, and the strided
    update of the (768, 50304) f32 param + two moments cost 12.2 ms/step
    — 7.5x its (50304, 768) embedding twin's 1.6 ms for identical bytes.
    Same math (the contraction just names the kernel's last axis), same
    vocab tensor-parallel sharding, same init variance (fan axes pinned).
    """

    cfg: LMConfig

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(in_axis=-1, out_axis=-2),
                ("vocab", "embed"),
            ),
            (self.cfg.vocab_size, self.cfg.d_model),
            jnp.float32,
        )
        if self.has_variable("params", "scale"):
            # weight-only int8 head (ops.quant.quantize_lm_params): int8
            # kernel streamed at the activation dtype, then the
            # per-vocab-row scale (V, 1) dequants the matmul output.
            # (An MXU-streamed Pallas matvec for this tiny-M apply was
            # built and measured SLOWER than XLA's multiply-reduce
            # lowering — ops/int8_matvec.py, PERF.md round 5.)
            return (
                jnp.einsum("...d,vd->...v", x, kernel.astype(x.dtype))
                * self.get_variable("params", "scale")[:, 0]
            )
        # f32 kernel: let the einsum promote (bf16 x, f32 kernel) -> f32
        # logits — casting the kernel down would round the loss edge
        return jnp.einsum("...d,vd->...v", x, kernel)


def make_lm_head(cfg: LMConfig) -> "LMHead":
    """The vocab projection ('lm_head') — see ``LMHead``."""
    return LMHead(cfg, name="lm_head")


def apply_final_norm_and_head(cfg: LMConfig, x, embed=None):
    """Final norm ('norm_f') + lm_head -> constrained f32 logits; under
    ``tie_embeddings`` the head is ``embed``'s own table.  Call inside an
    ``nn.compact`` method."""
    x = block_norm(cfg, "norm_f")(x).astype(jnp.float32)
    if cfg.tie_embeddings:
        with jax.named_scope("head"):
            logits = embed.attend(x)
    else:
        logits = make_lm_head(cfg)(x)
    return nn.with_logical_constraint(logits, ("batch", "act_seq", "act_vocab"))


class TransformerLM(nn.Module):
    """tokens (B, T) int32 -> (logits (B, T, V) f32, moe_aux_loss scalar).

    ``return_hidden=True`` stops after the final RMSNorm and returns the
    (B, T, D) pre-head activations instead of logits — the entry point for
    the chunked head+CE fusion (``ops/losses.fused_chunked_ce``), which
    applies the ``lm_head`` kernel chunk by chunk so the full logits
    tensor never exists.  Initialisation always takes the logits path, so
    the parameter tree (incl. ``lm_head``) is identical either way.
    """

    cfg: LMConfig
    attn_core: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True,
                 return_hidden: bool = False):
        cfg = self.cfg
        embed = make_embed(cfg)
        x = embed(tokens)
        x = nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))
        block = remat_block(cfg)
        aux_total = jnp.zeros((), jnp.float32)
        # what kept layers hand down the stack, beside x (LMConfig.carries)
        carry = {} if cfg.carries else None
        for i in range(cfg.n_layers):
            layer = block(cfg, self.attn_core, i, name=f"block{i}")
            if carry is None:
                x, aux = layer(x, None, deterministic)
            else:
                x, aux, carry = layer(x, None, deterministic, carry)
            aux_total = aux_total + aux
        if return_hidden:
            return block_norm(cfg, "norm_f")(x), aux_total
        return apply_final_norm_and_head(cfg, x, embed), aux_total


def count_lm_params(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
