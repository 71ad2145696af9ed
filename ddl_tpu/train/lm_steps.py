"""Jitted train/eval steps for the transformer LM family.

One jitted SPMD program per step, exactly like the CNN path
(``train/steps.py``), but over the 4-axis ``(data, seq, model, expert)``
mesh (``parallel/sharding.py``).  Parameter placement comes from the model's
logical axis annotations resolved through the rule table; XLA's partitioner
then inserts every collective the strategy needs — gradient all-reduce over
``data`` (the DDP reducer, reference ``ddp.py:127``), TP all-reduces over
``model``, MoE all-to-alls over ``expert``, FSDP all-gather/reduce-scatter
when ``fsdp=True`` — from sharding propagation alone.  The only manual
collective is ring attention's ``ppermute`` over ``seq``, injected as the
attention core inside an otherwise-auto jit program via ``shard_map``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddl_tpu.models.transformer import LMConfig, TransformerLM
from ddl_tpu.ops.flash_attention import flash_attention
from ddl_tpu.ops.quant import head_kernel
from ddl_tpu.parallel.ring_attention import make_ring_self_attention
# Jit-boundary specs + the family rule table come from the partition-
# rule engine — this module is lint-banned from hand-writing
# PartitionSpec axis literals (astlint 'pspec-hand-rolled').
from ddl_tpu.parallel.rules import (
    LM_MANUAL_ATTN_SPEC,
    PIPELINE_SCHEDULES,
    TOKEN_SPEC,
    lm_rules,
)
from ddl_tpu.parallel.sharding import (
    FLASH_AUTO_MIN_T,  # noqa: F401  (re-exported: measured dispatch bound)
    LMMeshSpec,
    build_lm_mesh,
    lm_logical_rules,
    normalize_flash,
    resolve_auto_flash,  # noqa: F401  (re-exported for tests/tools)
    validate_kv_head_sharding,
    validate_ulysses_kv_heads,
)
from ddl_tpu.parallel.ulysses import make_ulysses_self_attention

__all__ = [
    "LMTrainState",
    "LMStepFns",
    "STEP_PARTS",
    "TOKEN_SPEC",
    "make_lm_step_fns",
    "make_ring_core",
    "finalize_step_fns",
    "poison_nan_grads",
]


def poison_nan_grads(step, grads, nan_step: int | None):
    """Traced ``nan@grad`` fault injection, shared by the LM and ViT
    step factories: when ``nan_step`` (from
    ``faultinject.traced_nan_step()``, consumed at factory-build time)
    is armed, a ``lax.cond`` on the step counter replaces every gradient
    leaf with NaN at exactly that step — a real diverged update inside
    the compiled program.  No-op (and nothing traced in) when unarmed."""
    if nan_step is None:
        return grads
    return jax.lax.cond(
        step == nan_step,
        lambda g: jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), g),
        lambda g: g,
        grads,
    )

# The jit-boundary sharding for token batches (inputs AND targets):
# batch over data x expert, sequence over seq — defined once in
# parallel/rules.py (re-exported here for the factories' callers).


class LMTrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: optax.OptState


class LMStepFns(NamedTuple):
    """train(state, inputs, targets) -> (state, metrics);
    evaluate(state, inputs, targets) -> metrics;
    init_state() -> a fresh sharded LMTrainState; mesh: the device mesh.

    ``train`` donates its state argument (the TPU-memory-friendly pattern),
    so a state that has been passed to ``train`` is consumed — always
    rebind: ``state = fns.init_state()``, ``state, m = fns.train(state, ...)``.
    """

    train: Callable
    evaluate: Callable
    init_state: Callable
    mesh: Mesh


def make_ring_core(
    mesh: Mesh, causal: bool = True, use_flash: bool = False,
    window: int = 0,
) -> Callable:
    """Ring-attention core for injection into ``TransformerLM``: batch local
    per ``data`` shard, heads local per ``model`` shard, K/V rotating over
    the ``seq`` ring (``parallel/ring_attention.py``).  ``use_flash`` runs
    each per-device block through the Pallas kernel (flash inside ring —
    the long-context composition where T_local is itself long)."""
    return make_ring_self_attention(
        mesh,
        causal=causal,
        spec=LM_MANUAL_ATTN_SPEC,
        jit=False,
        use_flash=use_flash,
        window=window,
    )


def chunked_ce_loss(cfg, hidden, kernel, targets, aux, with_accuracy):
    """Shared tail of the ce_chunk / ce_vocab_chunk paths (flat loss and
    GPipe pipeline loss): fused chunked head+CE over post-norm hidden
    states — token-chunked (ops/losses.fused_chunked_ce) or
    vocab-streamed (fused_vocab_chunked_ce) per the config — assembled
    into the ``(loss, (None, metrics))`` contract ``finalize_step_fns``
    expects (``None`` logits signal the eval step that accuracy is already
    in the metrics).  Call inside an ``nn.logical_axis_rules`` scope."""
    from ddl_tpu.ops.losses import fused_chunked_ce

    if cfg.ce_vocab_chunk:
        from ddl_tpu.ops.losses import fused_vocab_chunked_ce

        ce, acc = fused_vocab_chunked_ce(
            hidden, kernel, targets, cfg.ce_vocab_chunk, with_accuracy
        )
    else:
        ce, acc = fused_chunked_ce(
            hidden,
            kernel,
            targets,
            cfg.ce_chunk,
            with_accuracy=with_accuracy,
            constrain=lambda z: nn.with_logical_constraint(
                z, ("batch", "act_seq", "act_vocab")
            ),
        )
    loss = ce + cfg.moe_aux_weight * aux
    metrics = {"loss": loss, "ce": ce, "moe_aux": aux}
    if acc is not None:
        metrics["accuracy"] = acc
    return loss, (None, metrics)


# The parts of an LM step, for the plan's second scope table
# (obs/scope.parts_table): {scope on an instruction's op_name path: its
# tag}.  The scopes are this package's own: flax names a module's scope
# after it (``attn``, ``mlp``, ``lm_head`` of models/transformer), and
# ``MoeMlp._dropless`` and ``loss_fn`` below open the rest by name.
STEP_PARTS = {
    **{f"moe/{part}": f"moe/{part}"
       for part in ("route", "dispatch", "experts", "shared", "combine")},
    "attn": "attn", "mlp": "mlp", "lm_head": "head", "head": "head",
    # a hybrid stack's mixers: the Mamba layer (projections, convolution,
    # gate) and inside it the scan kernels' calls, the Gated Memory Unit,
    # the cross-attention layers (``attn`` stays the self layers'); the
    # gated short convolution and inside it its elementwise part (the two
    # products and the taps).  The first scope on an op's path gives its
    # tag, so the Mamba layer's own ``ssm/conv`` stays under ``ssm``.
    "ssm": "ssm", "ssm/scan": "ssm/scan", "gmu": "gmu", "xattn": "xattn",
    "conv": "conv", "conv/short": "conv/short",
    # latent attention, and inside it the latent path (the latent's
    # projection and norm, K and V's up-projection, K's assembly)
    "mla": "mla", "mla/latent": "mla/latent",
}


def moe_router_metrics(intermediates) -> dict:
    """Aggregate the per-block router stats ``MoeMlp`` sows into scalar
    step metrics: mean token-drop fraction (capacity overflow silently
    drops tokens — a run must see it) and the expert-load spread
    (min/max share of kept token-choices; uniform = 1/E).

    Under gradient accumulation (``accum_steps > 1``) the step metrics are
    chunk means, so ``moe_load_max`` is a mean-of-maxes — it understates a
    single hot microbatch; watch per-chunk logs (accum=1) when hunting
    routing collapse."""
    drops, loads = [], []
    dropless = {"moe_local_rows": [], "moe_load_max_over_mean": [],
                "moe_rows_dropped": [], "moe_buffer_fill": [], "moe_topk_mass": []}
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        name = jax.tree_util.keystr(path)
        if "moe_drop_frac" in name:
            drops.append(leaf)
        elif "moe_expert_load" in name:
            loads.append(leaf)
        for key, leaves in dropless.items():
            if key in name:
                leaves.append(leaf)
    if dropless["moe_local_rows"]:
        # the dropless layers' counters: token-choices that landed on
        # held experts (all layers, a step), the worst layer's load of
        # its busiest held expert over the mean, rows not computed (0),
        # the share of the sorted buffer's row tiles in use (mean layer);
        # under softmax scores also the share of the softmax's mass that
        # the chosen experts hold before normalisation (mean layer)
        out = {
            "moe_local_rows": jnp.stack(dropless["moe_local_rows"]).sum(),
            "moe_load_max_over_mean": jnp.stack(
                dropless["moe_load_max_over_mean"]).max(),
            "moe_rows_dropped": jnp.stack(dropless["moe_rows_dropped"]).sum(),
            "moe_buffer_fill": jnp.stack(dropless["moe_buffer_fill"]).mean(),
        }
        if dropless["moe_topk_mass"]:
            out["moe_topk_mass"] = jnp.stack(dropless["moe_topk_mass"]).mean()
        return out
    if not drops:
        return {}
    load = jnp.stack(loads).mean(0)
    return {
        "moe_drop_frac": jnp.stack(drops).mean(),
        "moe_load_max": load.max(),
        "moe_load_min": load.min(),
    }


def sown_metrics(intermediates) -> dict:
    """The step metrics out of what the layers sow: the router's
    (``moe_router_metrics``) and, for a stack with Mamba layers,
    ``ssm_state_absmax``, the largest ``|h|`` at a chunk's start of any
    layer's scan (a state that grows without bound shows here steps
    before the loss does)."""
    out = moe_router_metrics(intermediates)
    peaks = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates)
             if "ssm_state_absmax" in jax.tree_util.keystr(path)]
    if peaks:
        out["ssm_state_absmax"] = jnp.stack(peaks).max()
    return out


def _token_ce(logits, targets):
    """Mean next-token cross-entropy (f32, stable)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, targets[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    return (lse - picked).mean()


def dropout_step_key(rng: jax.Array, step) -> jax.Array:
    """Per-step dropout base key, decorrelated from init by the 0x0D0 fold.
    The non-pipelined paths hand it to flax as the ``dropout`` rng stream;
    the pipeline schedules fold in (microbatch, stage, layer) so a
    microbatch's mask is identical wherever and whenever its forward is
    (re)computed — forward-for-handoff, GPipe's autodiff replay, and 1F1B's
    backward-tick recompute all agree."""
    return jax.random.fold_in(jax.random.fold_in(rng, 0x0D0), step)


def dropout_kwargs(rng: jax.Array, step, rate: float) -> dict:
    """``model.apply`` kwargs for optional train-mode dropout: active iff a
    ``step`` is given and ``rate > 0``; the rng is derived from the
    builder's key via ``dropout_step_key``.  Single source shared by the LM
    and ViT paths."""
    train = step is not None and rate > 0.0
    if not train:
        return {"deterministic": True, "rngs": None}
    return {"deterministic": False, "rngs": {"dropout": dropout_step_key(rng, step)}}


def accumulate_grads(grad_fn, params, chunked_args, k: int):
    """Mean gradients and metrics of ``grad_fn(params, *chunk)`` over the
    ``k`` leading-axis chunks of ``chunked_args`` — ONE compiled
    forward+backward (the scan body), carry zero-initialised from
    ``eval_shape``.  Shared by the LM and ViT accumulation paths."""
    (_, (_, abs_m)), abs_g = jax.eval_shape(
        grad_fn, params, *(a[0] for a in chunked_args)
    )

    def zeros(tree):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tree)

    def body(carry, chunk):
        g_acc, m_acc = carry
        (_, (_, m)), g = grad_fn(params, *chunk)
        return (
            jax.tree.map(jnp.add, g_acc, g),
            jax.tree.map(jnp.add, m_acc, m),
        ), None

    (g, m), _ = jax.lax.scan(body, (zeros(abs_g), zeros(abs_m)), chunked_args)
    return jax.tree.map(lambda x: x / k, g), jax.tree.map(lambda x: x / k, m)


def finalize_step_fns(
    mesh: Mesh,
    tx: optax.GradientTransformation,
    loss_fn,
    create_state,
    rng: jax.Array,
    accum_steps: int = 1,
    manual_grad_fn=None,
    contract: dict | None = None,
    probe_inputs=None,
) -> LMStepFns:
    """Shared tail for the non-pipelined and pipelined LM paths: wrap a
    ``loss_fn(params, inputs, targets, step=None) -> (loss, (logits,
    metrics))`` and a ``create_state(rng)`` into jitted, donated,
    mesh-scoped step functions.  ``train`` passes ``state.step`` as
    ``step`` (dropout rng derivation); eval passes nothing
    (deterministic).

    ``accum_steps > 1`` splits the batch into that many equal chunks and
    accumulates their gradients inside one jitted step (``lax.scan``)
    before a single optimizer update — peak activation memory drops by the
    chunk factor.  For dense models the update equals the full-batch step
    exactly (mean-CE gradients of equal chunks average to the full-batch
    gradient; tested); with MoE the load-balancing aux loss is nonlinear
    in batch composition, so chunked routing statistics make it a close
    but not bitwise-equal approximation.

    ``manual_grad_fn(params, inputs, targets, step) -> (grads, metrics)``,
    when given, replaces autodiff of ``loss_fn`` in the train step — for
    paths that compute their gradients explicitly (the 1F1B pipeline
    schedule, whose interleaved backward cannot be derived by differentiating
    a forward pass).  ``loss_fn`` still drives evaluation.

    ``contract`` (a dict from ``RuleTable.contract``) overrides the
    default boundary contract — the family factories derive it from
    their rule table so the contract checker validates rules, not
    hand-specs.

    ``jax.set_mesh`` wraps every call because ``nn.with_logical_constraint``
    lowers to bare-PartitionSpec sharding constraints, which resolve against
    the ambient mesh at trace time.
    """
    tok_sharding = NamedSharding(mesh, TOKEN_SPEC)
    replicated = NamedSharding(mesh, P())
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    # single-pass fused Adam when the transformation offers it (and the
    # one place ZeRO's reduce-scatter/all-gather constraints live); the
    # grace-window rebuild (recovery.scale_tx) preserves it
    fused_apply = getattr(tx, "fused_apply", None)
    # fault injection, compiled IN: `nan@grad:K` bakes a traced cond on
    # the step counter into the jitted program, so nan_policy="recover"
    # is exercised against an actual non-finite update (consumed at
    # build time — the post-rollback rebuild compiles it out)
    from ddl_tpu.utils import faultinject

    nan_grad_step = faultinject.traced_nan_step()

    def train_step(state, inputs, targets):
        if manual_grad_fn is not None:
            grads, metrics = manual_grad_fn(
                state.params, inputs, targets, state.step
            )
        elif accum_steps == 1:
            (_, (_, metrics)), grads = grad_fn(
                state.params, inputs, targets, state.step
            )
        else:
            k = accum_steps
            b = inputs.shape[0]
            # the chunked batch is TOKEN_SPEC with a leading scan axis
            chunk_sh = NamedSharding(mesh, P(None, *TOKEN_SPEC))
            inp_c = jax.lax.with_sharding_constraint(
                inputs.reshape(k, b // k, *inputs.shape[1:]), chunk_sh
            )
            tgt_c = jax.lax.with_sharding_constraint(
                targets.reshape(k, b // k, *targets.shape[1:]), chunk_sh
            )
            # distinct dropout streams per chunk
            steps = state.step * k + jnp.arange(k)
            grads, metrics = accumulate_grads(
                grad_fn, state.params, (inp_c, tgt_c, steps), k
            )
        grads = poison_nan_grads(state.step, grads, nan_grad_step)
        if fused_apply is not None:
            new_params, new_opt = fused_apply(
                grads, state.opt_state, state.params
            )
        else:
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        return (
            state.replace(
                step=state.step + 1, params=new_params, opt_state=new_opt
            ),
            metrics,
        )

    def eval_step(state, inputs, targets):
        _, (logits, metrics) = loss_fn(state.params, inputs, targets)
        if logits is None:  # fused CE path computed accuracy in-pass
            return dict(metrics)
        acc = (jnp.argmax(logits, -1) == targets).mean()
        return dict(metrics, accuracy=acc)

    from ddl_tpu.parallel.mesh import with_ambient_mesh

    def _with_mesh(fn):
        return with_ambient_mesh(mesh, fn)

    create = _with_mesh(jax.jit(create_state))
    train = _with_mesh(
        jax.jit(
            train_step,
            in_shardings=(None, tok_sharding, tok_sharding),
            out_shardings=(None, replicated),
            donate_argnums=(0,),
        )
    )
    evaluate = _with_mesh(
        jax.jit(
            eval_step,
            in_shardings=(None, tok_sharding, tok_sharding),
            out_shardings=replicated,
        )
    )
    # machine-readable sharding contract: what this factory promises at
    # its jit boundary, validated by `ddl_tpu lint` (analysis/contracts).
    # Factories pass their rule-table-derived contract (the default
    # covers pipeline callers); optimizer facts are stamped here where
    # the transformation is in hand.
    _zero = getattr(tx, "zero", None)
    train.contract = dict(
        contract if contract is not None else lm_rules().contract(),
        fused_optimizer_update=fused_apply is not None,
        zero_sharding=_zero is not None,
        zero_threshold=_zero.resolved_threshold() if _zero is not None else None,
    )
    # abstract batch structs at an arbitrary batch size, for the
    # compiled-IR probes (analysis/hlolint.py): lowering the same
    # program at two batch shapes and diffing structural fingerprints
    # is how shape-specialized constants are caught
    train.probe_inputs = probe_inputs
    return LMStepFns(
        train=train,
        evaluate=evaluate,
        init_state=lambda: create(rng),
        mesh=mesh,
    )


def make_lm_step_fns(
    cfg: LMConfig,
    spec: LMMeshSpec,
    tx: optax.GradientTransformation,
    rng: jax.Array,
    batch: int,
    seq_len: int,
    devices=None,
    num_microbatches: int = 0,
    accum_steps: int = 1,
    pipeline_schedule: str = "gpipe",
    virtual_stages: int = 1,
    zero_sharding: bool = False,
) -> LMStepFns:
    """Build the sharded train state and jitted step functions.

    ``zero_sharding`` attaches ZeRO-1 weight-update sharding to a fused
    Adam ``tx`` (``train/fused_optim.with_zero`` over the family rule
    table): large leaves' moments and update live on a 1/dp shard of
    ``data``.  Requires the flat (non-pipelined) path and a fused Adam.

    ``batch`` must divide by ``spec.data`` and ``seq_len`` by ``spec.seq``
    (static SPMD shapes).  The manual attention cores are head-parallel over
    ``model``, so ``attn_impl='ring'`` and ``'ulysses'`` need ``cfg.n_heads``
    divisible by ``spec.model``; ``'ulysses'`` additionally needs the local
    head count ``n_heads / model`` divisible by ``spec.seq`` (its all-to-all
    splits heads across the sequence axis).

    With ``spec.pipe > 1`` this delegates to the pipeline-parallel
    implementation (``parallel/lm_pipeline.py``), which runs the decoder
    stack as a GPipe schedule over the ``pipe`` mesh axis with
    ``num_microbatches`` microbatches per step (0 = default to one
    microbatch per stage).
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if pipeline_schedule not in PIPELINE_SCHEDULES:
        raise ValueError(f"unknown pipeline schedule {pipeline_schedule!r}")
    cfg = normalize_flash(cfg, spec, seq_len)
    validate_kv_head_sharding(cfg, spec)
    if cfg.ce_vocab_chunk and spec.model > 1:
        raise ValueError(
            f"ce_vocab_chunk={cfg.ce_vocab_chunk} requires mesh model=1 "
            "(the vocab scan slices the head kernel; use ce_chunk, whose "
            "per-chunk matmul shards over 'model')"
        )
    if cfg.ce_chunk and spec.seq > 1:
        raise ValueError(
            f"ce_chunk={cfg.ce_chunk} requires mesh seq=1 (the chunked CE "
            "scans over sequence positions, which conflicts with sequence "
            "sharding — and under SP the per-device logits are already "
            "T/seq smaller, so use the dense CE there)"
        )
    if spec.pipe > 1:
        if accum_steps > 1:
            raise ValueError(
                "accum_steps > 1 is the non-pipelined path's microbatching; "
                "with spec.pipe > 1 use num_microbatches instead"
            )
        if zero_sharding:
            raise ValueError(
                "zero_sharding requires the flat (non-pipelined) step: "
                "the pipeline schedule applies its optimizer inside a "
                "manual shard_map region where the ZeRO sharding "
                "constraints cannot be planted"
            )
        from ddl_tpu.parallel.lm_pipeline import make_lm_pipeline_step_fns

        return make_lm_pipeline_step_fns(
            cfg,
            spec,
            tx,
            rng,
            batch,
            seq_len,
            num_microbatches=num_microbatches or spec.pipe,
            devices=devices,
            schedule=pipeline_schedule,
            virtual_stages=virtual_stages,
        )
    if pipeline_schedule != "gpipe":
        raise ValueError(
            f"pipeline_schedule={pipeline_schedule!r} requires a pipe mesh "
            "axis (spec.pipe > 1)"
        )
    if virtual_stages != 1:
        raise ValueError(
            f"virtual_stages={virtual_stages} requires a pipe mesh axis "
            "(spec.pipe > 1)"
        )
    if num_microbatches > 1:
        raise ValueError(
            f"num_microbatches={num_microbatches} requires a pipe mesh axis "
            "(spec.pipe > 1); the non-pipelined step has no microbatching"
        )
    if accum_steps > 1:
        if batch % accum_steps:
            raise ValueError(
                f"batch {batch} % accum_steps {accum_steps} != 0"
            )
        if (batch // accum_steps) % (spec.data * spec.expert):
            raise ValueError(
                f"accumulation chunk {batch // accum_steps} must divide by "
                f"mesh data*expert={spec.data * spec.expert} (batch shards "
                "over both)"
            )
    if cfg.attn_impl not in ("dense", "ring", "ulysses"):
        raise ValueError(
            f"unknown attn_impl {cfg.attn_impl!r} "
            "(expected 'dense', 'ring', or 'ulysses')"
        )
    if not cfg.causal and (cfg.attn_impl != "dense" or cfg.flash):
        raise ValueError(
            "causal=False (bidirectional encoder) is only implemented for "
            "the XLA dense attention path; the ring/Ulysses/flash cores "
            "are built causal"
        )
    if batch % (spec.data * spec.expert):
        raise ValueError(
            f"batch {batch} must divide by mesh data*expert="
            f"{spec.data * spec.expert} (batch shards over both axes — "
            "outside MoE layers the expert axis is extra data parallelism)"
        )
    if seq_len % spec.seq:
        raise ValueError(f"seq_len {seq_len} must divide by mesh seq={spec.seq}")
    uses_manual_core = cfg.attn_impl in ("ring", "ulysses") or cfg.flash
    if uses_manual_core and cfg.n_heads % spec.model:
        raise ValueError(
            f"n_heads {cfg.n_heads} must divide by mesh model={spec.model} "
            "for the head-parallel manual attention cores"
        )
    if cfg.attn_impl == "ulysses" and (cfg.n_heads // spec.model) % spec.seq:
        raise ValueError(
            f"local head count {cfg.n_heads // spec.model} (n_heads/model) "
            f"must divide by mesh seq={spec.seq} for Ulysses all-to-all "
            "attention (use attn_impl='ring' otherwise)"
        )
    if cfg.attn_impl == "ulysses":
        validate_ulysses_kv_heads(cfg, spec)
    if cfg.num_experts and cfg.num_experts % spec.expert:
        raise ValueError(
            f"num_experts {cfg.num_experts} must divide by mesh "
            f"expert={spec.expert}"
        )
    if cfg.flash and cfg.attn_impl == "dense" and spec.seq > 1:
        raise ValueError(
            "flash=True with attn_impl='dense' requires mesh seq=1 "
            "(the kernel attends within one device's sequence; use "
            "attn_impl='ulysses' to combine flash with sequence parallelism)"
        )
    mesh = build_lm_mesh(spec, devices)
    rules = lm_logical_rules(cfg.fsdp)
    # batch over data AND expert — the same placement as the 'batch'
    # logical rule, so the manual attention cores see the local batch
    # shard instead of forcing an ep-fold replication at their boundary
    manual_spec = LM_MANUAL_ATTN_SPEC
    if cfg.layer_types and cfg.attn_impl != "dense":
        raise ValueError(
            "layer_types (sliding and full layers mixed) is built for the "
            "dense and flash cores; ring and Ulysses bind one window"
        )
    if cfg.attn_impl == "ring":
        attn_core = make_ring_core(
            mesh, use_flash=bool(cfg.flash), window=cfg.attn_window
        )
    elif cfg.attn_impl == "ulysses":
        attn_core = make_ulysses_self_attention(
            mesh,
            causal=True,
            spec=manual_spec,
            jit=False,
            attn_fn=flash_attention if cfg.flash else None,
            window=cfg.attn_window,
        )
    elif cfg.flash:
        # dense + flash: manual shard_map so the Pallas call sees the local
        # (batch, full seq, local heads) block — GSPMD cannot partition a
        # custom kernel, so it must live inside the manual region.
        def flash_core(window):
            return jax.shard_map(
                partial(flash_attention, causal=True, window=window),
                mesh=mesh,
                in_specs=(manual_spec,) * 3,
                out_specs=manual_spec,
                check_vma=False,
            )

        if cfg.layer_types:
            # one core a kind of layer; Attention asks by its own window
            cores = {w: flash_core(w) for w in {
                cfg.layer_window(i) for i in range(cfg.n_layers)}}
            attn_core = lambda q, k, v, window: cores[window](q, k, v)  # noqa: E731
        else:
            attn_core = flash_core(cfg.attn_window)
    else:
        attn_core = None
    model = TransformerLM(cfg, attn_core)

    dummy = jnp.zeros((batch, seq_len), jnp.int32)

    def init_params(rng):
        return model.init(rng, dummy)["params"]

    abs_params = jax.eval_shape(init_params, rng)
    # parameter placement from the family rule table (regex over param
    # path, parallel/rules.py) — leaf-for-leaf the resolution the
    # model's logical annotations used to produce, but declarative,
    # probe-validated, and the base the ZeRO shard derivation reads
    table = lm_rules(cfg.fsdp)
    abs_unboxed = nn.meta.unbox(abs_params)
    param_specs = table.specs(abs_unboxed)
    param_shardings = table.shardings(abs_unboxed, mesh)
    if zero_sharding:
        from ddl_tpu.train.fused_optim import with_zero

        tx = with_zero(tx, mesh, param_specs)

    def create_state(rng):
        params = nn.meta.unbox(init_params(rng))
        params = jax.lax.with_sharding_constraint(params, param_shardings)
        return LMTrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
        )

    def loss_fn(params, inputs, targets, step=None):
        kw = dropout_kwargs(rng, step, cfg.dropout_rate)
        # MoE runs also collect the router stats MoeMlp sows (drop
        # fraction, expert load) into the step metrics, and a stack with
        # Mamba layers its scans' largest state
        sows = bool(cfg.num_experts) or "mamba" in cfg.layer_types
        mutable = ["intermediates"] if sows else False
        router = {}
        with nn.logical_axis_rules(rules):
            if cfg.ce_chunk or cfg.ce_vocab_chunk:
                # chunked head+CE fusion: the model stops at the final
                # norm and the vocab projection runs chunk by chunk inside
                # the loss — the (B, T, V) logits never materialise
                # (ops/losses.fused_chunked_ce token-chunked, or
                # fused_vocab_chunked_ce vocab-streamed).  Eval
                # (step=None) folds next-token accuracy into the pass.
                out = model.apply(
                    {"params": params},
                    inputs,
                    deterministic=kw["deterministic"],
                    rngs=kw["rngs"],
                    return_hidden=True,
                    mutable=mutable,
                )
                if sows:
                    (hidden, aux), col = out
                    router = sown_metrics(col["intermediates"])
                else:
                    hidden, aux = out
                loss, (none, metrics) = chunked_ce_loss(
                    cfg, hidden, head_kernel(params["lm_head"]), targets, aux,
                    with_accuracy=step is None,
                )
                return loss, (none, dict(metrics, **router))
            out = model.apply(
                {"params": params},
                inputs,
                deterministic=kw["deterministic"],
                rngs=kw["rngs"],
                mutable=mutable,
            )
            if sows:
                (logits, aux), col = out
                router = sown_metrics(col["intermediates"])
            else:
                logits, aux = out
        with jax.named_scope("head"):
            ce = _token_ce(logits, targets)
        loss = ce + cfg.moe_aux_weight * aux
        metrics = {"loss": loss, "ce": ce, "moe_aux": aux, **router}
        return loss, (logits, metrics)

    return finalize_step_fns(
        mesh, tx, loss_fn, create_state, rng, accum_steps=accum_steps,
        contract=table.contract(),
        probe_inputs=lambda n=batch: (
            jax.ShapeDtypeStruct((n, seq_len), jnp.int32),
            jax.ShapeDtypeStruct((n, seq_len), jnp.int32),
        ),
    )
