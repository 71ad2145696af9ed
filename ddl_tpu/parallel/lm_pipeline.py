"""Pipeline parallelism for the transformer LM family.

The CNN path implements GPipe fully manually over a ``(data, pipe)`` mesh
(``parallel/pipeline.py`` — every collective hand-placed inside one
``shard_map``).  The transformer family instead expresses TP / SP / EP /
FSDP as *logical-axis rules* resolved by XLA's SPMD partitioner
(``parallel/sharding.py``), and this module adds the pipeline axis without
giving that up: the GPipe clock loop runs inside a **partial-manual**
``jax.shard_map`` that is manual over ``pipe`` only (``axis_names={'pipe'}``)
— stage handoffs are explicit ``lax.ppermute`` hops, while everything inside
a stage (batch over ``data``, sequence over ``seq``, heads/MLP over
``model``, experts over ``expert``, FSDP parameter sharding) stays in auto
mode and is partitioned by GSPMD exactly as in the non-pipelined path.

This is the composition the reference builds by hand out of NCCL subgroups
plus a DDP wrapper per pipeline stage (``ddp_n_pp.py:139-155``), extended to
the axes its design cannot express, with no subgroup bookkeeping at all.

Design (scan-over-ticks, stage-stacked params):

* the ``n_layers`` decoder blocks are split into ``pipe`` equal stages;
  per-stage block params are **stacked** on a leading stage axis and sharded
  ``P('pipe', ...)`` — each device holds only its own stage's parameters and
  optimizer state (unlike the CNN pipeline, which replicates the full tuple
  and switches on stage index).  Gradients and Adam state inherit the same
  sharding, so pipeline parallelism here also shards memory.
* embedding and LM head run *outside* the manual region in plain GSPMD land
  (they are cheap next to the block stack; MaxText's pipeline makes the same
  cut).  Their gradients arrive through the shard_map transpose: the
  embedded microbatch array enters replicated-over-pipe, so its cotangent is
  the pipe-psum of per-device cotangents — only stage 0 contributes.
* the GPipe schedule is a ``lax.scan`` over ``T = M + P - 1`` clock ticks.
  Every device runs its stage every tick (the off-schedule ticks are the
  GPipe bubble); there is no ``lax.switch`` because stages are uniform.
  Stage 0 reads microbatch ``t`` from the embedded input; others read the
  ``ppermute``'d boundary buffer.  The last stage's outputs accumulate into
  a per-microbatch buffer; off-schedule writes land on clamped indices that
  later valid writes overwrite, so no masking is needed on the data path.
* the backward schedule is autodiff through the scan: each ``ppermute``
  transposes into the reverse hop and the ticks replay backwards — the same
  property the CNN pipeline exploits (``parallel/pipeline.py``).  The
  hand-written alternatives interleave forward and backward in one scan:
  ``make_blocks_pipeline_1f1b`` (joint per-tick ``jax.vjp``) and
  ``make_blocks_pipeline_zb`` (zero-bubble: the vjp split into an
  activation-cotangent B pass on the critical path and a weight-gradient
  W pass deferred through a per-stage queue into the cooldown ticks).
* per-stage MoE aux losses leave the manual region as a ``P('pipe')``-sharded
  ``(pipe,)`` vector and are summed outside, keeping loss reductions out of
  the differentiated manual region (psum-under-grad transposes into a psum
  and scales cotangents — the trap documented in ``train/steps.py``).

Sequence parallelism composes through **nested** partial-manual shard_maps:
the ring / Ulysses attention cores become inner ``shard_map``s that inherit
the context mesh (no ``mesh=`` argument) and are manual over ``seq`` only —
their ``ppermute`` / ``all_to_all`` collectives run over the ``seq`` axis
while batch and heads stay auto-partitioned over ``data``/``model`` by
GSPMD, inside the outer manual-over-``pipe`` region.  ``flash=True``
composes the same way but needs the nested region *fully* manual over
(data, seq, model): GSPMD cannot auto-partition a Pallas custom call, so
the kernel instead runs on fully-local operands — the non-pipelined path's
manual attention region, minus ``pipe``.  ``n_layers`` must divide evenly
into ``pipe`` stages and the batch into ``num_microbatches * data`` shards.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddl_tpu.models.transformer import (
    LMConfig,
    TransformerLM,
    apply_final_norm_and_head,
    make_embed,
    remat_block,
)
from ddl_tpu.ops.losses import onehot_cross_entropy_mean
from ddl_tpu.ops.quant import head_kernel
from ddl_tpu.parallel.buffers import masked_slice_update, masked_slot_update
from ddl_tpu.parallel.sharding import (
    PIPE_AXIS,
    LMMeshSpec,
    build_lm_mesh,
    lm_logical_rules,
    normalize_flash,
    validate_kv_head_sharding,
    validate_ulysses_kv_heads,
)
from ddl_tpu.train.lm_steps import (
    LMStepFns,
    LMTrainState,
    _token_ce,
    chunked_ce_loss,
    dropout_step_key,
    finalize_step_fns,
)

__all__ = [
    "make_lm_pipeline_step_fns",
    "make_blocks_pipeline",
    "make_blocks_pipeline_1f1b",
    "make_blocks_pipeline_interleaved",
    "make_blocks_pipeline_zb",
    "blocks_pipeline_api",
    "split_lm_params",
    "merge_lm_params",
    "convert_lm_state",
    "abstract_lm_state",
    "saved_pipe_stages",
    "saved_virtual_stages",
]


def _mb_stage_key(step_key, mb_idx, s):
    """Dropout key for one (microbatch, stage) — the single fold chain both
    schedules share.  GPipe-vs-1F1B mask equality (and hence their gradient
    parity with dropout on, ``tests/test_dropout.py``) requires this exact
    derivation at every call site; never fork it per schedule."""
    return jax.random.fold_in(jax.random.fold_in(step_key, mb_idx), s)


def _make_stage_fn(block_mod: nn.Module, dropout: bool = False):
    """Stage forward: scan ``block_mod`` over the stage's stacked layer
    params.  Returns ``(y, aux)`` with ``aux`` the f32 sum of the stage's
    per-layer aux losses (MoE load balancing).

    With ``dropout=True`` the returned ``stage_fn(stage_blocks, x, key)``
    takes a per-(microbatch, stage) base key and folds the layer index in
    per scan step — the mask is a pure function of that key, so every
    recomputation of the same microbatch's forward (GPipe's autodiff
    replay, 1F1B's backward-tick vjp) reproduces it exactly."""
    if not dropout:

        def stage_fn(stage_blocks, x):
            def layer(carry, p):
                # full positional signature (x, cache, deterministic):
                # nn.remat's static_argnums for `deterministic` indexes
                # positional args
                y, aux = block_mod.apply({"params": p}, carry, None, True)
                return y, aux

            y, auxs = lax.scan(layer, x, stage_blocks)
            return y, auxs.astype(jnp.float32).sum()

        return stage_fn

    def stage_fn(stage_blocks, x, key):
        lps = jax.tree.leaves(stage_blocks)[0].shape[0]

        def layer(carry, xs):
            p, i = xs
            # deterministic rides positionally (arg 3) so nn.remat's
            # static_argnums sees it as a Python bool, not a tracer
            y, aux = block_mod.apply(
                {"params": p},
                carry,
                None,
                False,
                rngs={"dropout": jax.random.fold_in(key, i)},
            )
            return y, aux

        y, auxs = lax.scan(layer, x, (stage_blocks, jnp.arange(lps)))
        return y, auxs.astype(jnp.float32).sum()

    return stage_fn


def make_blocks_pipeline(
    mesh: Mesh,
    block_mod: nn.Module,
    *,
    n_stages: int,
    num_microbatches: int,
    mb: int,
    d_model: int,
    compute_dtype,
    dropout: bool = False,
):
    """The GPipe clock loop over a stack of uniform decoder/encoder blocks,
    as a partial-manual shard_map (manual over ``pipe`` only) — shared by
    the LM (``make_lm_pipeline_step_fns``) and ViT
    (``train/vit_steps.py``) pipelines.

    Returns ``pipeline(blocks_stacked, x_mb)`` where ``blocks_stacked`` is
    the ``(pipe, layers_per_stage, ...)`` param stack sharded
    ``P('pipe', ...)`` and ``x_mb`` is ``(M, mb, T, d_model)`` microbatched
    activations; yields ``(acc, aux_vec)`` with ``acc`` the last stage's
    per-microbatch outputs (callers slice ``[-1]``) and ``aux_vec`` the
    ``(pipe,)`` per-stage aux-loss vector.  See the module docstring for
    the schedule design.

    With ``dropout=True`` the callable takes a trailing per-step base key
    (``pipeline(blocks_stacked, x_mb, step_key)``) and each (microbatch,
    stage, layer) gets a deterministic mask folded from it (bubble-tick
    draws land on clamped microbatch indices whose outputs are overwritten
    or never read, so they are harmless).
    """
    M = num_microbatches
    d = d_model
    stage_fn = _make_stage_fn(block_mod, dropout)

    def pipeline_body(blocks_stacked, x_mb, *step_key):
        stage_blocks = jax.tree.map(lambda a: a[0], blocks_stacked)
        s = lax.axis_index(PIPE_AXIS)
        t_len = x_mb.shape[2]
        buf0 = jnp.zeros((mb, t_len, d), compute_dtype)
        acc0 = jnp.zeros((M, mb, t_len, d), compute_dtype)

        def tick(carry, t):
            buf, acc, aux = carry
            mb_idx = jnp.clip(t - s, 0, M - 1)
            x_first = lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, M - 1), 0, keepdims=False
            )
            x_in = jnp.where(s == 0, x_first, buf)
            if dropout:
                key = _mb_stage_key(step_key[0], mb_idx, s)
                out, aux_t = stage_fn(stage_blocks, x_in, key)
            else:
                out, aux_t = stage_fn(stage_blocks, x_in)
            valid = (t >= s) & (t - s < M)
            aux = aux + jnp.where(valid, aux_t, 0.0)
            # Off-schedule writes land on clamped indices; the valid write
            # for microbatch i happens at tick P-1+i, after any clamped
            # garbage, so the final buffer needs no masking (and only the
            # last pipe coordinate's buffer is ever read).
            acc = lax.dynamic_update_index_in_dim(
                acc, out, jnp.clip(t - (n_stages - 1), 0, M - 1), 0
            )
            buf = lax.ppermute(
                out, PIPE_AXIS, [(i, i + 1) for i in range(n_stages - 1)]
            )
            return (buf, acc, aux), None

        init = (buf0, acc0, jnp.zeros((), jnp.float32))
        (_, acc, aux), _ = lax.scan(tick, init, jnp.arange(M + n_stages - 1))
        return acc[None], aux[None]

    return jax.shard_map(
        pipeline_body,
        mesh=mesh,
        in_specs=(P(PIPE_AXIS), P()) + ((P(),) if dropout else ()),
        out_specs=(P(PIPE_AXIS), P(PIPE_AXIS)),
        axis_names={PIPE_AXIS},
        check_vma=False,
    )


def make_blocks_pipeline_interleaved(
    mesh: Mesh,
    block_mod: nn.Module,
    *,
    n_stages: int,
    virtual: int,
    num_microbatches: int,
    mb: int,
    d_model: int,
    compute_dtype,
    dropout: bool = False,
):
    """Interleaved (virtual-stage) pipeline clock loop: device ``s`` holds
    ``V = virtual`` non-contiguous layer chunks — global stage
    ``sigma = c*P + s`` — so each microbatch laps the device ring V times
    (Megatron-LM's interleaved schedule).  The pipeline fill/drain bubble
    shrinks by V: the schedule closes in ``M*V + P - 1`` ticks of
    1/V-stage work vs GPipe's ``M + P - 1`` ticks of full-stage work —
    same total compute, bubble fraction (P-1)/(MV+P-1) vs (P-1)/(M+P-1) —
    at the cost of V-1 extra wrap hops per microbatch.

    Schedule: microbatches advance in groups of P (``M % P == 0``
    required).  Within group ``g``, device ``s`` runs chunk ``c`` on
    group-microbatch ``r`` at tick ``t = g*V*P + c*P + r + s`` — unit
    ``(m, sigma)`` depends on ``(m, sigma-1)`` finishing one tick earlier
    on device ``s-1`` (or on device P-1's previous chunk via the wrap hop
    P-1 -> 0), and consecutive groups tile with no inter-group bubble.
    The boundary ``ppermute`` is the full ring including the wrap; the
    backward schedule is autodiff through the scan, as in
    ``make_blocks_pipeline``.

    Interface matches ``make_blocks_pipeline`` with ``blocks_stacked``
    shaped ``(P, V, layers_per_chunk, ...)`` sharded ``P('pipe', ...)``;
    the caller slices ``acc[-1]`` for the last global stage's outputs.
    """
    P_, V, M = n_stages, virtual, num_microbatches
    d = d_model
    stage_fn = _make_stage_fn(block_mod, dropout)

    def pipeline_body(blocks_stacked, x_mb, *step_key):
        local_chunks = jax.tree.map(lambda a: a[0], blocks_stacked)  # (V,lps,..)
        s = lax.axis_index(PIPE_AXIS)
        t_len = x_mb.shape[2]
        VP = V * P_
        buf0 = jnp.zeros((mb, t_len, d), compute_dtype)
        acc0 = jnp.zeros((M, mb, t_len, d), compute_dtype)

        def tick(carry, t):
            buf, acc, aux = carry
            rel = t - s
            g = jnp.clip(rel // VP, 0, M // P_ - 1)
            u = jnp.clip(rel - g * VP, 0, VP - 1)
            c = u // P_
            r = u - c * P_
            m = jnp.clip(g * P_ + r, 0, M - 1)
            valid = (rel >= 0) & (rel < M * V)
            chunk = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
                local_chunks,
            )
            x_first = lax.dynamic_index_in_dim(x_mb, m, 0, keepdims=False)
            x_in = jnp.where((s == 0) & (c == 0), x_first, buf)
            if dropout:
                key = _mb_stage_key(step_key[0], m, c * P_ + s)
                out, aux_t = stage_fn(chunk, x_in, key)
            else:
                out, aux_t = stage_fn(chunk, x_in)
            aux = aux + jnp.where(valid, aux_t, 0.0)
            # Last-global-stage output lands at acc[m].  As in the plain
            # GPipe loop, no masking: within a group every chunk writes the
            # same m range in increasing-u order, so chunk V-1's valid
            # write is last; later groups only touch later m; only the
            # last pipe coordinate's acc is ever read.
            acc = lax.dynamic_update_index_in_dim(acc, out, m, 0)
            # full ring: the wrap P-1 -> 0 carries the chunk c -> c+1
            # boundary back to device 0
            buf = lax.ppermute(
                out, PIPE_AXIS, [(i, (i + 1) % P_) for i in range(P_)]
            )
            return (buf, acc, aux), None

        init = (buf0, acc0, jnp.zeros((), jnp.float32))
        (_, acc, aux), _ = lax.scan(
            tick, init, jnp.arange(M * V + P_ - 1)
        )
        return acc[None], aux[None]

    return jax.shard_map(
        pipeline_body,
        mesh=mesh,
        in_specs=(P(PIPE_AXIS), P()) + ((P(),) if dropout else ()),
        out_specs=(P(PIPE_AXIS), P(PIPE_AXIS)),
        axis_names={PIPE_AXIS},
        check_vma=False,
    )


def blocks_pipeline_api(virtual: int):
    """(make_pipe, wrap_blocks, blocks_of) for a virtual-stage count — the
    single source both step builders (LM and ViT) use to pick the clock
    loop and apply/strip the self-describing ``{"interleaved": ...}``
    layout marker, so the three pieces cannot drift apart."""
    if virtual > 1:
        from functools import partial

        return (
            partial(make_blocks_pipeline_interleaved, virtual=virtual),
            lambda blocks: {"interleaved": blocks},
            lambda blocks: blocks["interleaved"],
        )
    return make_blocks_pipeline, (lambda b: b), (lambda b: b)


def make_blocks_pipeline_1f1b(
    mesh: Mesh,
    block_mod: nn.Module,
    head_loss,
    *,
    n_stages: int,
    num_microbatches: int,
    mb: int,
    d_model: int,
    compute_dtype,
    aux_cotangent: float,
    zero_metrics,
    dropout: bool = False,
    virtual: int = 1,
):
    """One-forward-one-backward interleaved schedule over the uniform block
    stack — the forward AND backward pipeline in a single scan, with the loss
    fused into the last stage (the piece GPipe-by-autodiff keeps outside).

    Because forward and backward interleave, this cannot be expressed as
    autodiff through the forward scan (that *is* GPipe); the backward is
    hand-written with per-tick ``jax.vjp``, the same construction as the CNN
    pipeline's 1F1B (``parallel/pipeline.py::per_device_train_1f1b``), lifted
    to the partial-manual region: everything inside a stage stays GSPMD-auto
    over data/seq/model/expert while ticks and hops are manual over ``pipe``.

    Schedule: at tick ``t`` the device at pipe coordinate ``s`` runs the
    forward of microbatch ``t - s`` and the backward of microbatch
    ``t - (2(P-1) - s)``; on the last stage these coincide, and the loss
    epilogue supplies the output cotangent in place of the (absent) next
    stage's reverse hop.  Activations ride a forward ``ppermute``,
    cotangents the reverse one; stage inputs wait for their backward in a
    ring buffer of depth ``min(2(P-1)+1, M)`` — O(P), independent of the
    microbatch count, vs the GPipe scan's O(M) saved per-tick stage inputs —
    and the schedule closes in ``M + 2(P-1)`` ticks vs autodiff-GPipe's
    ``2(M + P - 1)``.  The O(P) bound covers the *stage-activation*
    residency only: the embedded input ``x_mb`` and its cotangent
    accumulator ``dx_acc`` are full-batch ``(M, mb, T, d)`` buffers under
    either schedule — they are the embed/head edge, not pipeline state.

    ``head_loss(head_params, y, tgt) -> (loss_contribution, metrics)`` is the
    caller's last-stage epilogue (e.g. final-norm + vocab projection + CE/M
    for the LM); ``metrics`` must match ``zero_metrics`` in structure and is
    accumulated over microbatches.  ``aux_cotangent`` is the weight each
    stage's summed aux loss carries in the total loss (MoE balancing:
    ``moe_aux_weight / M``).

    Returns ``pipeline(blocks_stacked, head_params, x_mb, tgt_mb) ->
    (d_blocks, d_head, dx_mb, metrics, aux_sum)`` where ``d_blocks`` is
    ``P('pipe')``-stacked like its primal, and ``d_head``/``dx_mb``/
    ``metrics``/``aux_sum`` are pipe-replicated (``dx_mb`` is the cotangent
    of the embedded input — the caller backpropagates it through the
    embedding with its own ``jax.vjp``, closing the gradient path that
    autodiff's shard_map transpose handles on the GPipe path).  Gradients are
    numerically equivalent to the GPipe schedule (tested to 1e-5 by
    ``tests/test_lm_pipeline.py``): same math and microbatch order, though
    the last-stage CE uses a different formulation.

    ``virtual > 1`` runs the *interleaved* 1F1B (Megatron's combined
    schedule): device ``s`` holds ``V`` non-contiguous chunks (global stage
    ``sigma = c*P + s``, same placement as
    ``make_blocks_pipeline_interleaved``), the forward follows that
    schedule's group-of-P timing ``t_f = g*V*P + c*P + r + s``, and the
    backward mirrors it at ``t_b = (VP-1) + g*V*P + (V-1-c)*P + r +
    (P-1-s)`` — for ``V = 1`` these reduce exactly to the timetable above
    (``t_b = m + 2(P-1) - s``).  The schedule closes in ``MV + VP + P - 2``
    ticks of 1/V-stage fwd+bwd work vs autodiff-interleaved-GPipe's
    ``2(MV + P - 1)``, and stage-input residency is ``V * min(2VP, M)``
    microbatch buffers vs the GPipe scan's ``M * V``.  Requires
    ``M % P == 0`` (microbatches advance in groups of P, like the
    interleaved forward); ``blocks_stacked`` leaves are
    ``(P, V, layers_per_chunk, ...)``.
    """
    P_, V, M = n_stages, virtual, num_microbatches
    last = P_ - 1
    VP = V * P_
    d = d_model
    raw_stage_fn = _make_stage_fn(block_mod, dropout)
    if V == 1:
        # A microbatch's stage input is written at tick f+s and consumed by
        # its backward at tick f+2(P-1)-s: lifetime 2(P-1-s) ticks, so depth
        # 2(P-1)+1 (stage 0's worst case) always suffices; M slots suffice
        # when M is smaller because at most M microbatches are in flight.
        depth = min(2 * last + 1, M)
        n_ticks = M + 2 * last
        # forward handoff crosses stage boundaries only; no wrap traffic
        fwd_ring = [(i, i + 1) for i in range(last)]
        bwd_ring = [(i + 1, i) for i in range(last)]
    else:
        # interleaved: worst-case input lifetime is 2VP-2 ticks (chunk 0,
        # device 0); consecutive microbatches of one chunk are >= 1 tick
        # apart, so min(2VP, M) slots (both multiples of P) suffice.
        depth = min(2 * VP, M)
        n_ticks = M * V + VP + P_ - 2
        # full rings: the wrap carries chunk boundaries (c -> c+1 forward
        # on P-1 -> 0, and the reverse on 0 -> P-1)
        fwd_ring = [(i, (i + 1) % P_) for i in range(P_)]
        bwd_ring = [((i + 1) % P_, i) for i in range(P_)]

    def pipeline_body(blocks_stacked, head_params, x_mb, tgt_mb, *step_key):
        local_blocks = jax.tree.map(lambda a: a[0], blocks_stacked)
        s = lax.axis_index(PIPE_AXIS)
        t_len = x_mb.shape[2]

        def tick(carry, t):
            fwd_buf, bwd_buf, resid, dx_acc, g_blocks, g_head, met, aux = carry
            if V == 1:
                c_f = c_b = 0
                f_idx = jnp.clip(t - s, 0, M - 1)
                fwd_valid = (t >= s) & (t - s < M)
                off = 2 * last - s
                b_idx = jnp.clip(t - off, 0, M - 1)
                bwd_valid = (t >= off) & (t - off < M)
                chunk_f = chunk_b = local_blocks
            else:
                rel_f = t - s
                g_f = jnp.clip(rel_f // VP, 0, M // P_ - 1)
                u_f = jnp.clip(rel_f - g_f * VP, 0, VP - 1)
                c_f = u_f // P_
                f_idx = jnp.clip(g_f * P_ + (u_f - c_f * P_), 0, M - 1)
                fwd_valid = (rel_f >= 0) & (rel_f < M * V)
                rel_b = t - (VP - 1) - (last - s)
                g_b = jnp.clip(rel_b // VP, 0, M // P_ - 1)
                u_b = jnp.clip(rel_b - g_b * VP, 0, VP - 1)
                cp = u_b // P_
                c_b = (V - 1) - cp
                b_idx = jnp.clip(g_b * P_ + (u_b - cp * P_), 0, M - 1)
                bwd_valid = (rel_b >= 0) & (rel_b < M * V)
                chunk_f = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(
                        a, c_f, 0, keepdims=False
                    ),
                    local_blocks,
                )
                chunk_b = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(
                        a, c_b, 0, keepdims=False
                    ),
                    local_blocks,
                )

            if dropout:
                # the same (microbatch, global stage) key on the forward
                # tick and on the backward tick's recompute — identical
                # masks, exact gradients (matches interleaved GPipe keying)
                fwd_stage_fn = lambda blocks, x: raw_stage_fn(
                    blocks, x, _mb_stage_key(step_key[0], f_idx, c_f * P_ + s)
                )
                bwd_stage_fn = lambda blocks, x: raw_stage_fn(
                    blocks, x, _mb_stage_key(step_key[0], b_idx, c_b * P_ + s)
                )
            else:
                fwd_stage_fn = bwd_stage_fn = raw_stage_fn

            x_first = lax.dynamic_index_in_dim(x_mb, f_idx, 0, keepdims=False)
            x_in = jnp.where((s == 0) & (c_f == 0), x_first, fwd_buf)
            if V == 1:
                resid = masked_slot_update(
                    resid, x_in, f_idx % depth, fwd_valid
                )
                x_b = lax.dynamic_index_in_dim(
                    resid, b_idx % depth, 0, keepdims=False
                )
            else:
                resid = masked_slice_update(
                    resid,
                    x_in[None, None],
                    (c_f, f_idx % depth, 0, 0, 0),
                    fwd_valid,
                )
                x_b = lax.dynamic_slice(
                    resid,
                    (c_b, b_idx % depth, 0, 0, 0),
                    (1, 1, mb, t_len, d),
                )[0, 0]
            tgt_b = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, b_idx, 0, keepdims=False),
                tgt_mb,
            )

            # Every collective-bearing computation runs UNCONDITIONALLY on
            # every device: the forward-for-handoff and the stage vjp both
            # contain the nested seq cores' ppermute / all_to_all (and the
            # MoE dispatch), which XLA compiles as single whole-mesh
            # channel ops — inside a branch that only some pipe coordinates
            # take, the other coordinates never join the rendezvous and the
            # program deadlocks (observed).  Only the head epilogue sits in
            # a cond: its collectives (TP/data/seq all-reduces from GSPMD)
            # are per-group ops whose groups lie within one pipe
            # coordinate, so every participant agrees on the branch.
            out, _ = fwd_stage_fn(chunk_f, x_in)
            (y_b, aux_b), stage_vjp = jax.vjp(bwd_stage_fn, chunk_b, x_b)

            def last_branch(y):
                # the loss supplies the output cotangent: vjp through
                # head_loss in place of the (absent) next stage's hop
                _, head_vjp, m = jax.vjp(
                    lambda hp, yy: head_loss(hp, yy, tgt_b),
                    head_params,
                    y,
                    has_aux=True,
                )
                dh, g_y = head_vjp(jnp.ones((), jnp.float32))
                return dh, g_y.astype(y.dtype), m

            def mid_branch(y):
                # cotangent arrived from stage s+1 on the reverse hop
                dh = jax.tree.map(jnp.zeros_like, head_params)
                return dh, bwd_buf.astype(y.dtype), zero_metrics

            # head epilogue on the last GLOBAL stage (device P-1, chunk V-1)
            dh, g_y, m = lax.cond(
                (s == last) & (c_b == V - 1), last_branch, mid_branch, y_b
            )
            db, dx = stage_vjp(
                (g_y, jnp.asarray(aux_cotangent, jnp.float32))
            )

            def acc(old, new):
                return jax.tree.map(
                    lambda o, n: o + jnp.where(bwd_valid, n, jnp.zeros_like(n)),
                    old,
                    new,
                )

            if V == 1:
                g_blocks = acc(g_blocks, db)
            else:
                # scatter-accumulate this tick's chunk gradient at c_b
                g_blocks = jax.tree.map(
                    lambda g, n: lax.dynamic_update_index_in_dim(
                        g,
                        lax.dynamic_index_in_dim(g, c_b, 0, keepdims=False)
                        + jnp.where(bwd_valid, n, jnp.zeros_like(n)),
                        c_b,
                        0,
                    ),
                    g_blocks,
                    db,
                )
            g_head, met = acc(g_head, dh), acc(met, m)
            aux = aux + jnp.where(bwd_valid, aux_b, 0.0)
            dx_acc = masked_slot_update(
                dx_acc, dx, b_idx, bwd_valid & (s == 0) & (c_b == 0)
            )
            fwd_buf = lax.ppermute(
                out.astype(compute_dtype), PIPE_AXIS, fwd_ring
            )
            bwd_buf = lax.ppermute(
                dx.astype(compute_dtype), PIPE_AXIS, bwd_ring
            )
            return (fwd_buf, bwd_buf, resid, dx_acc, g_blocks, g_head, met, aux), None

        buf0 = jnp.zeros((mb, t_len, d), compute_dtype)
        resid_shape = (
            (depth, mb, t_len, d) if V == 1 else (V, depth, mb, t_len, d)
        )
        init = (
            buf0,
            buf0,
            jnp.zeros(resid_shape, compute_dtype),
            jnp.zeros((M, mb, t_len, d), compute_dtype),
            jax.tree.map(jnp.zeros_like, local_blocks),
            jax.tree.map(jnp.zeros_like, head_params),
            zero_metrics,
            jnp.zeros((), jnp.float32),
        )
        (_, _, _, dx_acc, g_blocks, g_head, met, aux), _ = lax.scan(
            tick, init, jnp.arange(n_ticks)
        )
        # stage grads stay pipe-stacked like their primal; everything else
        # lives on one coordinate (head/metrics on the last, dx on the
        # first) and the psum broadcasts it pipe-replicated
        g_blocks = jax.tree.map(lambda g: g[None], g_blocks)
        g_head = jax.tree.map(lambda g: lax.psum(g, PIPE_AXIS), g_head)
        dx_acc = lax.psum(dx_acc, PIPE_AXIS)
        met = jax.tree.map(lambda x: lax.psum(x, PIPE_AXIS), met)
        aux = lax.psum(aux, PIPE_AXIS)
        return g_blocks, g_head, dx_acc, met, aux

    return jax.shard_map(
        pipeline_body,
        mesh=mesh,
        in_specs=(P(PIPE_AXIS), P(), P(), P()) + ((P(),) if dropout else ()),
        out_specs=(P(PIPE_AXIS), P(), P(), P(), P()),
        axis_names={PIPE_AXIS},
        check_vma=False,
    )


def make_blocks_pipeline_zb(
    mesh: Mesh,
    block_mod: nn.Module,
    head_loss,
    *,
    n_stages: int,
    num_microbatches: int,
    mb: int,
    d_model: int,
    compute_dtype,
    aux_cotangent: float,
    zero_metrics,
    dropout: bool = False,
):
    """Zero-bubble (ZB-H1-style) schedule: the 1F1B clock loop with the
    full per-tick backward split into its two halves — the activation
    cotangent (**B**) stays on the critical path, the weight gradient
    (**W**) is deferred into a per-stage queue and drained during the
    ticks the stage would otherwise idle.

    The 1F1B tick runs one joint ``jax.vjp`` per tick: cotangents for
    the stage *input* (which the reverse hop needs THIS tick — the next
    stage's backward blocks on it) and for the stage *weights* (which
    nothing consumes until the optimizer update after the scan) are
    computed together, so the weight half of the backward sits on the
    inter-stage critical path for no reason.  Here the B pass is a
    ``jax.vjp`` w.r.t. the stage input only (weights closed over) and
    the W pass a ``jax.vjp`` w.r.t. the weights only (input closed
    over), applied to the SAME output cotangent — by linearity of the
    vjp in which inputs are held fixed, the two halves are exactly the
    joint vjp's two components, so gradients match GPipe/1F1B to float
    tolerance (``tests/test_lm_pipeline.py`` asserts <= 1e-6).

    Schedule: F and B keep the 1F1B timetable — at tick ``t`` stage
    ``s`` runs the forward of microbatch ``t - s`` and the B pass of
    microbatch ``t - (2(P-1) - s)`` — and the scan still closes in
    ``M + 2(P-1)`` ticks.  Each B tick enqueues its W work item (the
    stage input, the output cotangent, and the microbatch index for the
    dropout-key refold) into a ring queue of ``min(P-1, M) + 1`` slots;
    one item drains per tick when the queue is over its deferral
    capacity or the stage's B schedule has gone quiet.  The capacity is
    the stage's tail-idle tick count: stage ``s`` finishes its B passes
    ``s`` ticks before the scan ends (its last B is at tick
    ``M - 1 + 2(P-1) - s``), so deferring up to ``s`` W passes lands
    them exactly in the cooldown ticks where 1F1B computes nothing —
    the ZB-H1 move of filling the drain bubble with weight-gradient
    work.  Every queued item is drained by the final tick (steady state
    is one-in-one-out above capacity; the tail holds at most ``s``
    items and has ``s`` ticks), so no microbatch's weight gradient is
    dropped, and items drain oldest-first — microbatch order, the same
    accumulation order as 1F1B.

    On the uniform-tick SPMD realisation every device still executes
    every slot every tick, so the win is *modeled*, not wall-clock on a
    sim mesh: ``obs/schedule_model.py`` quantifies it (zb idles half of
    1F1B's stage-time at t_F = t_B = t_W), ``obs trace --step`` renders
    the lanes, and the PERF.md round-19 protocol banks the chip number.
    Memory: the queue adds ``2 * (min(P-1, M) + 1)`` microbatch-sized
    buffers on top of 1F1B's ``min(2(P-1)+1, M)``-deep stage-input ring
    — still O(P), independent of M.

    Dropout masks are a pure function of ``_mb_stage_key(step_key,
    microbatch, stage)``; the W pass refolds the key from the queued
    microbatch index, so the forward-for-handoff, the B-tick recompute,
    and the deferred W-tick recompute all draw the identical mask —
    schedule-invariant gradients, same fold chain as GPipe/1F1B.

    Interface matches ``make_blocks_pipeline_1f1b`` with ``virtual=1``
    (the B/W split is single-chunk; virtual stages compose with 1F1B).
    """
    P_, M = n_stages, num_microbatches
    last = P_ - 1
    d = d_model
    raw_stage_fn = _make_stage_fn(block_mod, dropout)
    depth = min(2 * last + 1, M)
    n_ticks = M + 2 * last
    # W queue slots: the in-flight count peaks at cap_s + 1 = s + 1
    # (enqueue lands before the over-capacity drain), bounded by M + 1
    # when M is smaller than the deepest capacity
    K = min(last, M) + 1
    fwd_ring = [(i, i + 1) for i in range(last)]
    bwd_ring = [(i + 1, i) for i in range(last)]

    def pipeline_body(blocks_stacked, head_params, x_mb, tgt_mb, *step_key):
        local_blocks = jax.tree.map(lambda a: a[0], blocks_stacked)
        s = lax.axis_index(PIPE_AXIS)
        t_len = x_mb.shape[2]
        cap = jnp.minimum(s, M)  # deferral depth = stage s's tail-idle ticks

        def tick(carry, t):
            (fwd_buf, bwd_buf, resid, dx_acc, g_blocks, g_head, met, aux,
             qx, qg, qm, q_tail, q_len) = carry
            f_idx = jnp.clip(t - s, 0, M - 1)
            fwd_valid = (t >= s) & (t - s < M)
            off = 2 * last - s
            b_idx = jnp.clip(t - off, 0, M - 1)
            bwd_valid = (t >= off) & (t - off < M)

            if dropout:
                fwd_stage_fn = lambda blocks, x: raw_stage_fn(
                    blocks, x, _mb_stage_key(step_key[0], f_idx, s)
                )
                bwd_stage_fn = lambda blocks, x: raw_stage_fn(
                    blocks, x, _mb_stage_key(step_key[0], b_idx, s)
                )
            else:
                fwd_stage_fn = bwd_stage_fn = raw_stage_fn

            x_first = lax.dynamic_index_in_dim(x_mb, f_idx, 0, keepdims=False)
            x_in = jnp.where(s == 0, x_first, fwd_buf)
            resid = masked_slot_update(resid, x_in, f_idx % depth, fwd_valid)
            x_b = lax.dynamic_index_in_dim(
                resid, b_idx % depth, 0, keepdims=False
            )
            tgt_b = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, b_idx, 0, keepdims=False),
                tgt_mb,
            )

            # As in the 1F1B loop, every collective-bearing computation
            # runs unconditionally on every device (nested seq cores /
            # MoE dispatch compile to whole-mesh channel ops); only the
            # head epilogue sits in a cond.
            out, _ = fwd_stage_fn(local_blocks, x_in)
            # B: input-cotangent-only vjp — the stage params are closed
            # over, so this computes exactly the dx half of 1F1B's
            # joint vjp and nothing of the weight half
            (y_b, aux_b), b_vjp = jax.vjp(
                lambda x: bwd_stage_fn(local_blocks, x), x_b
            )

            def last_branch(y):
                _, head_vjp, m = jax.vjp(
                    lambda hp, yy: head_loss(hp, yy, tgt_b),
                    head_params,
                    y,
                    has_aux=True,
                )
                dh, g_y = head_vjp(jnp.ones((), jnp.float32))
                return dh, g_y.astype(y.dtype), m

            def mid_branch(y):
                dh = jax.tree.map(jnp.zeros_like, head_params)
                return dh, bwd_buf.astype(y.dtype), zero_metrics

            dh, g_y, m = lax.cond(s == last, last_branch, mid_branch, y_b)
            (dx,) = b_vjp(
                (g_y, jnp.asarray(aux_cotangent, jnp.float32))
            )

            def acc(old, new):
                return jax.tree.map(
                    lambda o, n: o + jnp.where(bwd_valid, n, jnp.zeros_like(n)),
                    old,
                    new,
                )

            g_head, met = acc(g_head, dh), acc(met, m)
            aux = aux + jnp.where(bwd_valid, aux_b, 0.0)
            dx_acc = masked_slot_update(
                dx_acc, dx, b_idx, bwd_valid & (s == 0)
            )

            # enqueue this tick's W work: the stage input, the output
            # cotangent, and the microbatch index (dropout-key refold)
            slot = q_tail % K
            qx = masked_slot_update(qx, x_b, slot, bwd_valid)
            qg = masked_slot_update(
                qg, g_y.astype(compute_dtype), slot, bwd_valid
            )
            qm = masked_slot_update(qm, b_idx, slot, bwd_valid)
            q_tail = q_tail + bwd_valid.astype(jnp.int32)
            q_len = q_len + bwd_valid.astype(jnp.int32)

            # drain the oldest item when over the deferral capacity or
            # when the B schedule has gone quiet (the cooldown ticks)
            do_drain = (q_len > 0) & ((q_len > cap) | ~bwd_valid)
            head_slot = (q_tail - q_len) % K
            xw = lax.dynamic_index_in_dim(qx, head_slot, 0, keepdims=False)
            gw = lax.dynamic_index_in_dim(qg, head_slot, 0, keepdims=False)
            mw = lax.dynamic_index_in_dim(qm, head_slot, 0, keepdims=False)
            if dropout:
                w_stage_fn = lambda blocks, x: raw_stage_fn(
                    blocks, x, _mb_stage_key(step_key[0], mw, s)
                )
            else:
                w_stage_fn = raw_stage_fn
            # W: weight-cotangent-only vjp at the queued (input,
            # cotangent) — the dual closure of the B pass; runs
            # unconditionally (collectives), accumulated under the
            # drain mask
            (y_w, _aux_w), w_vjp = jax.vjp(
                lambda blocks: w_stage_fn(blocks, xw), local_blocks
            )
            (db,) = w_vjp(
                (gw.astype(y_w.dtype), jnp.asarray(aux_cotangent, jnp.float32))
            )
            g_blocks = jax.tree.map(
                lambda g, n: g + jnp.where(do_drain, n, jnp.zeros_like(n)),
                g_blocks,
                db,
            )
            q_len = q_len - do_drain.astype(jnp.int32)

            fwd_buf = lax.ppermute(
                out.astype(compute_dtype), PIPE_AXIS, fwd_ring
            )
            bwd_buf = lax.ppermute(
                dx.astype(compute_dtype), PIPE_AXIS, bwd_ring
            )
            return (fwd_buf, bwd_buf, resid, dx_acc, g_blocks, g_head,
                    met, aux, qx, qg, qm, q_tail, q_len), None

        buf0 = jnp.zeros((mb, t_len, d), compute_dtype)
        init = (
            buf0,
            buf0,
            jnp.zeros((depth, mb, t_len, d), compute_dtype),
            jnp.zeros((M, mb, t_len, d), compute_dtype),
            jax.tree.map(jnp.zeros_like, local_blocks),
            jax.tree.map(jnp.zeros_like, head_params),
            zero_metrics,
            jnp.zeros((), jnp.float32),
            jnp.zeros((K, mb, t_len, d), compute_dtype),
            jnp.zeros((K, mb, t_len, d), compute_dtype),
            jnp.zeros((K,), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
        )
        (_, _, _, dx_acc, g_blocks, g_head, met, aux, *_), _ = lax.scan(
            tick, init, jnp.arange(n_ticks)
        )
        g_blocks = jax.tree.map(lambda g: g[None], g_blocks)
        g_head = jax.tree.map(lambda g: lax.psum(g, PIPE_AXIS), g_head)
        dx_acc = lax.psum(dx_acc, PIPE_AXIS)
        met = jax.tree.map(lambda x: lax.psum(x, PIPE_AXIS), met)
        aux = lax.psum(aux, PIPE_AXIS)
        return g_blocks, g_head, dx_acc, met, aux

    return jax.shard_map(
        pipeline_body,
        mesh=mesh,
        in_specs=(P(PIPE_AXIS), P(), P(), P()) + ((P(),) if dropout else ()),
        out_specs=(P(PIPE_AXIS), P(), P(), P(), P()),
        axis_names={PIPE_AXIS},
        check_vma=False,
    )


class _Embed(nn.Module):
    """Stage-0 prologue.  Uses ``make_embed`` — the same construction
    ``TransformerLM`` composes — so full-model checkpoints restructure 1:1
    (``split_lm_params``)."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, tokens):
        x = make_embed(self.cfg)(tokens)
        return nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))


class _Head(nn.Module):
    """Last-stage epilogue: final RMSNorm + vocab projection (shared
    construction with ``TransformerLM``)."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x):
        return apply_final_norm_and_head(self.cfg, x)


class _HeadNorm(nn.Module):
    """Norm-only view of the head params: applies ``norm_f`` and leaves the
    vocab projection to the chunked head+CE fusion
    (``ops/losses.fused_chunked_ce``) — apply with the same ``head`` param
    subtree as ``_Head`` (``lm_head`` simply goes unused)."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x):
        from ddl_tpu.models.transformer import RMSNorm

        return RMSNorm(self.cfg.dtype, self.cfg.norm_eps, name="norm_f")(x)


def stack_block_params(full_params: Any, n_stages: int, virtual: int = 1):
    """Stack a param tree's ``block{i}`` subtrees into the pipeline layout —
    the unit every blocks pipeline shards ``P('pipe', ...)``.  Shared by the
    LM and ViT splits.

    ``virtual == 1``: ``(n_stages, layers_per_stage, ...)``, stage-major
    (stage p owns layers ``[p*Lps, (p+1)*Lps)``).

    ``virtual > 1`` (interleaved schedule): ``(n_stages, virtual,
    layers_per_chunk, ...)`` with the Megatron virtual-stage assignment —
    global stage ``sigma = c*n_stages + s`` lives at ``[s, c]``, so device
    ``s`` owns the *non-contiguous* layer chunks ``{c*P+s : c}`` and a
    microbatch visits every device V times."""
    layer_keys = sorted(
        (k for k in full_params if k.startswith("block")),
        key=lambda k: int(k.removeprefix("block")),
    )
    lps = len(layer_keys) // (n_stages * virtual)

    def gather(*xs):
        a = jnp.stack(xs)
        if virtual == 1:
            return a.reshape(n_stages, lps, *xs[0].shape)
        # layer ell = (c*P + s)*lps + j  ->  reshape (V, P, lps) indexes
        # [c, s, j]; transpose to the device-major (P, V, lps) layout
        a = a.reshape(virtual, n_stages, lps, *xs[0].shape)
        return a.transpose(1, 0, *range(2, a.ndim))

    return jax.tree.map(gather, *(full_params[k] for k in layer_keys))


def split_lm_params(full_params: Any, n_stages: int, virtual: int = 1) -> dict:
    """Restructure a full ``TransformerLM`` param tree into the pipeline
    layout ``{embed, blocks, head}`` (see ``stack_block_params``).  With
    ``virtual > 1`` the stack nests under ``blocks["interleaved"]`` — a
    structural marker, so a snapshot records its own virtual-stage count
    (leading dims alone cannot distinguish (P, V, lps) from (P, lps);
    parameter ranks vary)."""
    blocks = stack_block_params(full_params, n_stages, virtual)
    return {
        "embed": {"embed": full_params["embed"]},
        "blocks": {"interleaved": blocks} if virtual > 1 else blocks,
        "head": {"norm_f": full_params["norm_f"], "lm_head": full_params["lm_head"]},
    }


def merge_lm_params(pp_params: dict) -> dict:
    """Inverse of ``split_lm_params``: pipeline layout ``{embed, blocks,
    head}`` back to the flat ``TransformerLM`` tree (``block{i}`` keyed).
    The interleaved layout is self-describing (the ``"interleaved"``
    wrapper plus the stack's (P, V, lps) leading dims)."""
    blocks = pp_params["blocks"]
    full = {
        "embed": pp_params["embed"]["embed"],
        "norm_f": pp_params["head"]["norm_f"],
        "lm_head": pp_params["head"]["lm_head"],
    }
    if not _is_interleaved_blocks(blocks):
        shape_leaf = jax.tree.leaves(blocks)[0]
        n_stages, lps = shape_leaf.shape[:2]
        for p in range(n_stages):
            for j in range(lps):
                full[f"block{p * lps + j}"] = jax.tree.map(
                    lambda x: x[p, j], blocks
                )
        return full
    blocks = blocks["interleaved"]
    n_stages, virtual, lps = jax.tree.leaves(blocks)[0].shape[:3]
    for c in range(virtual):
        for s in range(n_stages):
            for j in range(lps):
                ell = (c * n_stages + s) * lps + j
                full[f"block{ell}"] = jax.tree.map(lambda x: x[s, c, j], blocks)
    return full


def _is_interleaved_blocks(blocks) -> bool:
    return isinstance(blocks, dict) and "interleaved" in blocks


def _is_pipeline_tree(x) -> bool:
    return isinstance(x, dict) and set(x) == {"embed", "blocks", "head"}


def _is_full_tree(x) -> bool:
    return isinstance(x, dict) and "lm_head" in x and "block0" in x


def _map_param_subtrees(x, convert):
    """Apply ``convert`` to every param-layout dict inside an arbitrary
    optimizer-state structure (NamedTuples / tuples / lists / dicts of
    arrays and param-shaped trees, e.g. Adam's ``mu``/``nu``).  The layout
    checks run first so a param tree is converted whole, not recursed into."""
    if _is_pipeline_tree(x) or _is_full_tree(x):
        return convert(x)
    if isinstance(x, tuple) and hasattr(x, "_fields"):  # NamedTuple state
        return type(x)(*(_map_param_subtrees(f, convert) for f in x))
    if isinstance(x, (tuple, list)):
        return type(x)(_map_param_subtrees(f, convert) for f in x)
    if isinstance(x, dict):  # e.g. multi_transform's inner_states
        return {k: _map_param_subtrees(v, convert) for k, v in x.items()}
    return x


def saved_pipe_stages(params: Any) -> int:
    """Pipe stage count a params tree was written with (1 = full layout).
    Works on real trees and on checkpoint *metadata* trees (anything whose
    leaves carry ``.shape`` — see ``checkpoint.snapshot_metadata``), so a
    resuming run can discover a snapshot's layout without flags."""
    if _is_pipeline_tree(params):
        return int(jax.tree.leaves(params["blocks"])[0].shape[0])
    if not _is_full_tree(params):
        raise ValueError(
            f"unrecognized params layout (keys: {sorted(params)[:8]}...)"
            if isinstance(params, dict)
            else f"unrecognized params layout: {type(params)}"
        )
    return 1


def saved_virtual_stages(params: Any) -> int:
    """Virtual-stage (interleaved) count a params tree was written with
    (1 = plain stage-contiguous layout).  Like ``saved_pipe_stages``, works
    on metadata trees — the interleaved layout is marked structurally by
    the ``blocks["interleaved"]`` wrapper, so a resuming run discovers it
    from the snapshot itself."""
    if _is_pipeline_tree(params) and _is_interleaved_blocks(params["blocks"]):
        return int(
            jax.tree.leaves(params["blocks"]["interleaved"])[0].shape[1]
        )
    saved_pipe_stages(params)  # layout sanity check
    return 1


def abstract_lm_state(
    cfg: LMConfig,
    tx: optax.GradientTransformation,
    n_stages: int = 1,
    mesh: Mesh | None = None,
    virtual: int = 1,
) -> LMTrainState:
    """Shape/dtype skeleton of an ``LMTrainState`` in the given layout
    (``n_stages=1`` = full, ``>1`` = pipeline), for use as a restore target
    (``checkpoint.load_snapshot``) without building step functions, running
    an init on devices, or needing the saved run's mesh: param shapes depend
    only on ``cfg`` (RoPE — no seq-length-shaped params), so a snapshot's
    tree is reconstructible from config alone.

    Pass ``mesh`` (the *restoring* run's mesh) to attach replicated
    shardings to the skeleton — without it Orbax falls back to the sharding
    file written at save time, which only resolves on the exact saving
    topology.  The restored replicated arrays are then re-placed by
    ``convert_lm_state(..., like=...)``."""
    model = TransformerLM(cfg, None)
    dummy = jnp.zeros((1, 1), jnp.int32)

    def build(rng):
        params = nn.meta.unbox(model.init(rng, dummy)["params"])
        if n_stages > 1:
            params = split_lm_params(params, n_stages, virtual)
        return LMTrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
        )

    abstract = jax.eval_shape(build, jax.random.key(0))
    if mesh is not None:
        rep = NamedSharding(mesh, P())
        abstract = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
            abstract,
        )
    return abstract


def convert_lm_state(
    state: LMTrainState,
    *,
    n_stages: int | None = None,
    virtual: int = 1,
    like: LMTrainState | None = None,
) -> LMTrainState:
    """Convert an ``LMTrainState`` between the full (non-pipelined) and
    pipeline param layouts, including every param-shaped subtree of the
    optimizer state (Adam ``mu``/``nu`` mirror the param tree, so the same
    structural transform applies).

    Pass ``n_stages`` (and ``virtual`` for the interleaved schedule) to go
    full -> pipeline; omit ``n_stages`` to go pipeline -> full (interleaved
    layouts self-describe via the ``blocks["interleaved"]`` wrapper).  ``like`` (a state from the destination step functions'
    ``init_state``) re-places the converted arrays onto the destination
    mesh/shardings — required when the source and destination meshes
    differ.  Together with Orbax's mesh-elastic restore (``checkpoint.py``)
    this makes the parallelism topology a *resume-time* choice: a snapshot
    from a plain TP/FSDP run continues as a pipelined run and vice versa
    (``tests/test_lm_pipeline.py::test_lm_pipeline_checkpoint_interop``).
    """
    if n_stages is None:
        convert = merge_lm_params
        if not _is_pipeline_tree(state.params):
            raise ValueError(
                "state is not in pipeline layout; pass n_stages to convert "
                "full -> pipeline"
            )
    else:
        if not _is_full_tree(state.params):
            raise ValueError("state is not in full layout")
        convert = lambda p: split_lm_params(p, n_stages, virtual)
    out = state.replace(
        params=convert(state.params),
        opt_state=_map_param_subtrees(state.opt_state, convert),
    )
    if like is not None:
        out = jax.device_put(out, jax.tree.map(lambda x: x.sharding, like))
    return out


def make_lm_pipeline_step_fns(
    cfg: LMConfig,
    spec: LMMeshSpec,
    tx: optax.GradientTransformation,
    rng: jax.Array,
    batch: int,
    seq_len: int,
    num_microbatches: int,
    devices=None,
    schedule: str = "gpipe",
    virtual_stages: int = 1,
) -> LMStepFns:
    """Pipeline-parallel LM step functions (same interface as
    ``make_lm_step_fns``).  Requires ``spec.pipe > 1``.

    ``virtual_stages > 1`` selects the interleaved schedule
    (``make_blocks_pipeline_interleaved``): each device holds that many
    non-contiguous layer chunks, shrinking the pipeline bubble by the same
    factor.  Requires ``n_layers % (pipe * virtual_stages) == 0`` and
    ``num_microbatches % pipe == 0``; gpipe schedule only (the 1F1B
    interleave is not implemented for virtual stages).

    ``schedule``: ``"gpipe"`` (all forwards then all backwards, derived by
    autodiff of the forward scan), ``"1f1b"`` (explicit interleaved
    forward/backward, ``make_blocks_pipeline_1f1b`` — O(pipe) instead of
    O(microbatches) *stage-activation* residency; the embed/head edge
    buffers stay O(batch) under both schedules — same gradients), or
    ``"zb"`` (zero-bubble, ``make_blocks_pipeline_zb`` — the 1F1B clock
    loop with the backward split into B/W passes and the weight
    gradients deferred into the cooldown ticks; single-chunk only, so
    ``virtual_stages`` must be 1).  Evaluation always uses the
    forward-only GPipe schedule."""
    cfg = normalize_flash(cfg, spec, seq_len)  # resolve flash="auto"
    validate_kv_head_sharding(cfg, spec)
    n_stages, M = spec.pipe, num_microbatches
    V = virtual_stages
    if n_stages < 2:
        raise ValueError("make_lm_pipeline_step_fns needs spec.pipe >= 2")
    from ddl_tpu.parallel.rules import PIPELINE_SCHEDULES, lm_rules

    if schedule not in PIPELINE_SCHEDULES:
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    if cfg.ce_vocab_chunk and schedule in ("1f1b", "zb"):
        raise ValueError(
            f"ce_vocab_chunk is not supported with the {schedule.upper()} "
            "schedule (its per-microbatch head loss runs inside the manual "
            "region, where the vocab-scan custom VJP is unverified); use "
            "the GPipe schedule or ce_chunk"
        )
    if V < 1:
        raise ValueError(f"virtual_stages must be >= 1, got {V}")
    if schedule == "zb" and V > 1:
        raise ValueError(
            f"virtual_stages={V} requires schedule='gpipe' or '1f1b' "
            "(the zero-bubble B/W-split clock loop is single-chunk; "
            "compose virtual stages with 1f1b instead)"
        )
    if V > 1 and M % n_stages:
        raise ValueError(
            f"num_microbatches {M} % pipe {n_stages} != 0 (the interleaved "
            "schedule advances microbatches in groups of pipe)"
        )
    if cfg.attn_impl not in ("dense", "ring", "ulysses"):
        raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}")
    if not cfg.causal and (cfg.attn_impl != "dense" or cfg.flash):
        raise ValueError(
            "causal=False is only implemented for the XLA dense attention "
            "path (the nested ring/Ulysses/flash cores are built causal)"
        )
    if cfg.flash and cfg.attn_impl == "dense" and spec.seq > 1:
        raise ValueError(
            "flash=True with attn_impl='dense' requires mesh seq=1 "
            "(the kernel attends within one device's sequence; use "
            "attn_impl='ulysses' to combine flash with sequence parallelism)"
        )
    if cfg.flash and cfg.n_heads % spec.model:
        raise ValueError(
            f"n_heads {cfg.n_heads} % mesh model={spec.model} != 0 (the "
            "flash kernel runs head-local inside a fully-manual region)"
        )
    if cfg.attn_impl == "ulysses" and cfg.n_heads % spec.seq:
        raise ValueError(
            f"n_heads {cfg.n_heads} % mesh seq={spec.seq} != 0 (the nested "
            "Ulysses all-to-all splits the global head dim across seq)"
        )
    if cfg.n_layers % (n_stages * V):
        raise ValueError(
            f"n_layers {cfg.n_layers} % (pipe {n_stages} * virtual {V}) != 0"
        )
    if M < 1:
        raise ValueError(f"num_microbatches must be >= 1, got {M}")
    if batch % M:
        raise ValueError(f"batch {batch} % microbatches {M} != 0")
    mb = batch // M
    if mb % (spec.data * spec.expert):
        raise ValueError(
            f"microbatch {mb} must divide by mesh data*expert="
            f"{spec.data * spec.expert} (batch shards over both)"
        )
    if seq_len % spec.seq:
        raise ValueError(f"seq_len {seq_len} % mesh seq={spec.seq} != 0")
    if cfg.num_experts and cfg.num_experts % spec.expert:
        raise ValueError(
            f"num_experts {cfg.num_experts} % mesh expert={spec.expert} != 0"
        )
    if not cfg.layers_alike or cfg.moe_dropless or cfg.tie_embeddings:
        # one block module runs every layer of a stage over stacked
        # parameters, so layers that differ would all become layer 0; the
        # stages collect nothing sown, so a dropless layer's counters
        # (moe_rows_dropped) would be lost; and the embedding lives on the
        # first stage, the head on the last, so they cannot be one leaf
        raise NotImplementedError(
            "the pipeline stacks one block for all layers, carries no "
            "sown counters and holds embedding and head on different "
            "stages: layer_types, num_dense_layers, the dropless "
            "expert layer and tie_embeddings are built for "
            "make_lm_step_fns only"
        )
    mesh = build_lm_mesh(spec, devices)
    rules = lm_logical_rules(cfg.fsdp)

    # Sequence-parallel attention cores nest as inner shard_maps: no mesh
    # argument (they inherit the context mesh, in which 'pipe' is already
    # manual), manual over 'seq' only, specs naming only 'seq' — batch and
    # heads remain auto-partitioned over data/model by GSPMD.
    #
    # With ``flash=True`` the nested region must instead be manual over
    # every axis the kernel's operands touch (data, seq, model): GSPMD
    # cannot auto-partition a Pallas custom call, but a fully-local call
    # inside a fully-manual nested region needs no partitioning at all —
    # the same construction as the non-pipelined path's manual attention,
    # minus ``pipe`` (already manual in the enclosing region).
    seq_spec = P(None, "seq")
    # batch over data AND expert (the 'batch' logical rule): the fully-
    # manual flash regions must make 'expert' manual too, or XLA would
    # have to auto-partition the Pallas call over the residual expert
    # sharding (which GSPMD cannot do)
    manual_spec = P(("data", "expert"), "seq", "model", None)
    if cfg.flash:
        from functools import partial

        from ddl_tpu.ops.flash_attention import flash_attention

        if cfg.attn_impl == "ring":
            from ddl_tpu.parallel.ring_attention import ring_attention

            # flash inside ring, fully-manual like the other flash cores;
            # the ring coordinate rides in as data (axis_index cannot
            # lower inside nested manual regions)
            ring_flash_sm = jax.shard_map(
                lambda q, k, v, pos: ring_attention(
                    q, k, v, axis_name="seq", causal=True, pos=pos[0],
                    use_flash=True, window=cfg.attn_window,
                ),
                in_specs=(manual_spec,) * 3 + (P("seq"),),
                out_specs=manual_spec,
                axis_names={"data", "seq", "model", "expert"},
                check_vma=False,
            )

            def attn_core(q, k, v):
                return ring_flash_sm(
                    q, k, v, jnp.arange(spec.seq, dtype=jnp.int32)
                )
        else:
            if cfg.attn_impl == "ulysses":
                if (cfg.n_heads // spec.model) % spec.seq:
                    raise ValueError(
                        f"local head count {cfg.n_heads // spec.model} "
                        f"(n_heads/model) % mesh seq={spec.seq} != 0 for "
                        "flash-under-Ulysses (heads are model-local in the "
                        "fully-manual region)"
                    )
                validate_ulysses_kv_heads(cfg, spec)
                from ddl_tpu.parallel.ulysses import ulysses_attention

                inner = partial(
                    ulysses_attention,
                    axis_name="seq",
                    causal=True,
                    attn_fn=flash_attention,
                    window=cfg.attn_window,
                )
            else:  # dense + flash, seq=1: the kernel is the whole core
                inner = partial(
                    flash_attention, causal=True, window=cfg.attn_window
                )
            attn_core = jax.shard_map(
                inner,
                in_specs=(manual_spec,) * 3,
                out_specs=manual_spec,
                axis_names={"data", "seq", "model", "expert"},
                check_vma=False,
            )
    elif cfg.attn_impl == "ring":
        from ddl_tpu.parallel.ring_attention import ring_attention

        # The ring coordinate rides in as data (a P('seq')-sharded arange):
        # lax.axis_index cannot lower inside nested manual regions.
        ring_sm = jax.shard_map(
            lambda q, k, v, pos: ring_attention(
                q, k, v, axis_name="seq", causal=True, pos=pos[0],
                window=cfg.attn_window,
            ),
            in_specs=(seq_spec,) * 3 + (P("seq"),),
            out_specs=seq_spec,
            axis_names={"seq"},
            check_vma=False,
        )

        def attn_core(q, k, v):
            return ring_sm(q, k, v, jnp.arange(spec.seq, dtype=jnp.int32))

    elif cfg.attn_impl == "ulysses":
        from functools import partial

        from ddl_tpu.parallel.ulysses import ulysses_attention

        attn_core = jax.shard_map(
            partial(ulysses_attention, axis_name="seq", causal=True,
                    window=cfg.attn_window),
            in_specs=(seq_spec,) * 3,
            out_specs=seq_spec,
            axis_names={"seq"},
            check_vma=False,
        )
    else:
        attn_core = None
    block_cls = remat_block(cfg)
    block_mod = block_cls(cfg, attn_core)
    embed_mod = _Embed(cfg)
    head_mod = _Head(cfg)
    compute_dtype = cfg.dtype
    d = cfg.d_model

    use_dropout = cfg.dropout_rate > 0.0
    pipe_kwargs = dict(
        n_stages=n_stages,
        num_microbatches=M,
        mb=mb,
        d_model=d,
        compute_dtype=compute_dtype,
    )
    make_pipe, wrap_blocks, unwrap_blocks = blocks_pipeline_api(V)
    # deterministic instance (eval always; train when dropout is off)
    pipeline = make_pipe(mesh, block_mod, **pipe_kwargs)
    pipeline_drop = (
        make_pipe(mesh, block_mod, dropout=True, **pipe_kwargs)
        if use_dropout
        else None
    )

    mb_spec = NamedSharding(mesh, P(None, ("data", "expert"), "seq"))

    def blocks_of(params):
        return unwrap_blocks(params["blocks"])

    def forward(params, tokens, step=None, return_hidden=False):
        with nn.logical_axis_rules(rules):
            x = embed_mod.apply({"params": params["embed"]}, tokens)  # (B,T,D)
            x = x.reshape(M, mb, seq_len, d)
            x = lax.with_sharding_constraint(x, mb_spec)
            if use_dropout and step is not None:
                acc, aux_vec = pipeline_drop(
                    blocks_of(params), x, dropout_step_key(rng, step)
                )
            else:
                acc, aux_vec = pipeline(blocks_of(params), x)
            x_out = acc[-1].reshape(batch, seq_len, d)
            if return_hidden:  # norm only; the chunked CE applies the head
                out = _HeadNorm(cfg).apply({"params": params["head"]}, x_out)
            else:
                out = head_mod.apply({"params": params["head"]}, x_out)
        # Each (stage, microbatch) aux term is a mean over that microbatch's
        # rows; dividing the sum by M recovers the full-batch per-layer mean
        # the non-pipelined model computes.
        return out, aux_vec.sum() / M

    # ---- init: build the full (non-pipelined) model's params and
    # restructure, so pipeline and single-program checkpoints interconvert
    # and parity tests can share initialisation. ----
    dummy = jnp.zeros((batch, seq_len), jnp.int32)
    full_model = TransformerLM(cfg, None)

    def init_params(rng):
        full = nn.meta.unbox(full_model.init(rng, dummy)["params"])
        return split_lm_params(full, n_stages, V)

    # Shardings: embed/head from the logical rule table; stacked blocks get
    # ('pipe', None) prepended to each leaf's rule-resolved spec.
    abs_params = jax.eval_shape(lambda r: full_model.init(r, dummy)["params"], rng)
    logical = nn.get_partition_spec(abs_params)
    mesh_sharding = nn.logical_to_mesh_sharding(logical, mesh, rules)
    block0 = mesh_sharding["block0"]
    stack_dims = (None,) * (1 if V == 1 else 2)  # (lps,) or (V, lps)
    blocks_sharding = jax.tree.map(
        lambda sh: NamedSharding(mesh, P(PIPE_AXIS, *stack_dims, *sh.spec)),
        block0,
    )
    param_shardings = {
        "embed": {"embed": mesh_sharding["embed"]},
        "blocks": wrap_blocks(blocks_sharding),
        "head": {
            "norm_f": mesh_sharding["norm_f"],
            "lm_head": mesh_sharding["lm_head"],
        },
    }

    def create_state(rng):
        params = init_params(rng)
        params = jax.lax.with_sharding_constraint(params, param_shardings)
        return LMTrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
        )

    def loss_fn(params, inputs, targets, step=None):
        if cfg.ce_chunk or cfg.ce_vocab_chunk:
            # The GPipe head runs OUTSIDE the manual region on the full
            # (B, T, V) logits — the same loss-edge memory wall as the
            # flat path, fixed the same way: norm-only head, then the
            # chunked head+CE fusion, token-chunked or vocab-streamed
            # (shared tail: lm_steps.chunked_ce_loss).
            hidden, aux = forward(params, inputs, step, return_hidden=True)
            with nn.logical_axis_rules(rules):
                return chunked_ce_loss(
                    cfg, hidden, head_kernel(params["head"]["lm_head"]),
                    targets, aux, with_accuracy=step is None,
                )
        logits, aux = forward(params, inputs, step)
        ce = _token_ce(logits, targets)
        loss = ce + cfg.moe_aux_weight * aux
        return loss, (logits, {"loss": loss, "ce": ce, "moe_aux": aux})

    manual_grad_fn = None
    if schedule in ("1f1b", "zb"):
        # Loss inside the manual region: per-microbatch CE on the last
        # stage, contributing ce/M to the full-batch mean; the raw ce rides
        # out as a metric.
        def head_loss(head_p, y, tgt):
            with nn.logical_axis_rules(rules):
                if cfg.ce_chunk:
                    # chunked head+CE per microbatch, one-hot gather form
                    # (take_along_axis does not partition in manual
                    # subgroups — see onehot_cross_entropy_mean)
                    from ddl_tpu.ops.losses import fused_chunked_ce

                    hidden = _HeadNorm(cfg).apply({"params": head_p}, y)
                    ce, _ = fused_chunked_ce(
                        hidden,
                        head_kernel(head_p["lm_head"]),
                        tgt,
                        cfg.ce_chunk,
                        use_onehot=True,
                        constrain=lambda z: nn.with_logical_constraint(
                            z, ("batch", "act_seq", "act_vocab")
                        ),
                    )
                    return ce / M, ce
                logits = head_mod.apply({"params": head_p}, y)
            ce, _ = onehot_cross_entropy_mean(logits, tgt)
            return ce / M, ce

        bw_kwargs = dict(
            n_stages=n_stages,
            num_microbatches=M,
            mb=mb,
            d_model=d,
            compute_dtype=compute_dtype,
            aux_cotangent=cfg.moe_aux_weight / M,
            zero_metrics=jnp.zeros((), jnp.float32),
            dropout=use_dropout,
        )
        if schedule == "zb":
            pipeline_bw = make_blocks_pipeline_zb(
                mesh, block_mod, head_loss, **bw_kwargs
            )
        else:
            pipeline_bw = make_blocks_pipeline_1f1b(
                mesh, block_mod, head_loss, virtual=V, **bw_kwargs
            )

        def manual_grad_fn(params, inputs, targets, step=None):
            with nn.logical_axis_rules(rules):
                x, embed_vjp = jax.vjp(
                    lambda ep: embed_mod.apply({"params": ep}, inputs),
                    params["embed"],
                )
                x_mb = lax.with_sharding_constraint(
                    x.reshape(M, mb, seq_len, d), mb_spec
                )
                tgt_mb = lax.with_sharding_constraint(
                    targets.reshape(M, mb, seq_len),
                    NamedSharding(mesh, P(None, ("data", "expert"), "seq")),
                )
                key_args = (
                    (dropout_step_key(rng, step),) if use_dropout else ()
                )
                g_blocks, g_head, dx_mb, ce_sum, aux_sum = pipeline_bw(
                    blocks_of(params), params["head"], x_mb, tgt_mb, *key_args
                )
                # close the gradient path GPipe's shard_map transpose handles
                (g_embed,) = embed_vjp(
                    dx_mb.reshape(batch, seq_len, d).astype(x.dtype)
                )
            ce = ce_sum / M
            moe_aux = aux_sum / M
            loss = ce + cfg.moe_aux_weight * moe_aux
            grads = {
                "embed": g_embed,
                "blocks": wrap_blocks(g_blocks),
                "head": g_head,
            }
            return grads, {"loss": loss, "ce": ce, "moe_aux": moe_aux}

    # the family rule table's contract, extended with the pipeline facts
    # the zb contract probe (analysis/contracts.py) validates: which
    # schedule this factory compiled and its stage/chunk geometry
    contract = lm_rules(cfg.fsdp).contract(
        pipeline_schedule=schedule,
        pipeline_stages=n_stages,
        virtual_stages=V,
    )
    return finalize_step_fns(
        mesh, tx, loss_fn, create_state, rng, manual_grad_fn=manual_grad_fn,
        contract=contract,
        probe_inputs=lambda n=batch: (
            jax.ShapeDtypeStruct((n, seq_len), jnp.int32),
            jax.ShapeDtypeStruct((n, seq_len), jnp.int32),
        ),
    )
