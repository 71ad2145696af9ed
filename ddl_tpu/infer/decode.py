"""Autoregressive inference for the transformer LM family.

The reference's only inference surface is a loss-less pipeline schedule used
for evaluation (``pp.py:146-150``); it has no generation path at all.  A
complete framework needs one, so this module adds KV-cached autoregressive
decoding over the *training* parameter tree — no weight export step, no
separate inference model:

* ``Attention``/``Block`` (``models/transformer.py``) expose an incremental
  mode sharing the training parameters by construction (same submodule
  names), so any training snapshot — including one restructured from the
  pipeline layout by ``parallel.lm_pipeline.convert_lm_state`` — decodes
  as-is.
* The KV cache is a static-shape ``(B, prompt+max_new, H, Dh)`` buffer per
  layer, updated in place via ``dynamic_update_slice`` — XLA keeps the
  update in-place on TPU, and the whole generate loop is ONE jitted
  program: prefill, then ``lax.scan`` over decode steps (compiler-friendly
  control flow; no per-token dispatch from Python).
* Sharding: the same logical-axis rule table as training
  (``parallel/sharding.py``) — batch over ``data``, heads over ``model`` —
  so tensor-parallel decode works on the same mesh as the training run.
  Sampling happens on replicated logits.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding

from ddl_tpu.infer.kv_cache import init_kv_cache
from ddl_tpu.models.transformer import (
    Block,
    LMConfig,
    apply_final_norm_and_head,
    make_embed,
    refuse_cache_over_layer_types,
)
# Jit-boundary spec + the family rule table come from the partition-rule
# engine (parallel/rules.py); re-exported here for the generator's
# callers.
from ddl_tpu.parallel.rules import DECODE_TOKEN_SPEC, decode_rules
from ddl_tpu.parallel.sharding import (
    FLASH_AUTO_MIN_T,
    LMMeshSpec,
    build_lm_mesh,
    lm_logical_rules,
    validate_kv_head_sharding,
)

__all__ = [
    "LMDecode", "DECODE_TOKEN_SPEC", "init_kv_cache", "make_lm_generator",
    "prefill_attn_core", "sample_token",
]


class LMDecode(nn.Module):
    """One incremental forward over the full layer stack.

    ``tokens`` (B, T) — the prompt at prefill (T = prompt length) or the
    last sampled token during decode (T = 1); ``caches`` — one cache
    object a layer (``infer/kv_cache.py``, ``serve/kv_pool.PagedKV``),
    which knows the positions it already holds.  Returns (logits
    (B, T, V) f32, new caches).  Submodule names mirror ``TransformerLM``
    exactly, so the training param tree applies as-is.
    """

    cfg: LMConfig
    # attention core for the PREFILL pass only (e.g. the flash kernel —
    # prefill is a training-style causal forward over the prompt); decode
    # steps (T=1) always use cached dense attention.
    attn_core: Optional[Callable] = None

    @nn.compact
    def __call__(
        self, tokens, caches, last_only: bool = False, last_index=None,
    ):
        cfg = self.cfg
        embed = make_embed(cfg)
        x = embed(tokens)
        x = nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))
        new_caches = []
        for i in range(cfg.n_layers):
            x, _aux, c = Block(cfg, self.attn_core, i, name=f"block{i}")(
                x, caches[i]
            )
            new_caches.append(c)
        if last_index is not None:
            # right-padded prefill (serve/engine.py bucketing): the
            # next-token logits live at the TRUE prompt end, not at -1.
            # Slicing before the head keeps the norm+head computation the
            # (B, 1, D) shape last_only compiles, so a padded prefill's
            # logits stay bit-identical to the unpadded single-request
            # program's (a full-width head + post-hoc index fuses
            # differently and drifts enough to flip near-tie argmaxes)
            x = lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
        elif last_only:  # prefill only needs the next-token logits
            x = x[:, -1:]
        return apply_final_norm_and_head(cfg, x, embed), tuple(new_caches)


def prefill_attn_core(cfg: LMConfig, mesh, prompt_len: int):
    """The attention core of a prefill over ``prompt_len`` tokens: prefill
    is a training-style causal forward over the prompt, so it rides the
    flash kernel where training would (single-device mesh: GSPMD cannot
    partition a Pallas custom call, and multi-device decode keeps the
    dense prefill core inside its sharded program).  None = dense."""
    if mesh.size == 1 and cfg.causal and (
        cfg.flash is True
        or (cfg.flash == "auto" and prompt_len >= FLASH_AUTO_MIN_T)
    ):
        from ddl_tpu.ops.flash_attention import flash_attention

        return partial(flash_attention, causal=True, window=cfg.attn_window)
    return None


def sample_token(logits, rng, temperature: float, top_k: int | None):
    """(..., V) logits -> int32 tokens: argmax at ``temperature`` 0, else
    a draw from ``softmax(logits / temperature)`` over the ``top_k`` most
    likely.  One body for the generator's batch and the engine's lanes
    (vmapped there, a key a lane)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if top_k is not None:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(
        rng, logits / jnp.float32(temperature), axis=-1
    ).astype(jnp.int32)


def make_lm_generator(
    cfg: LMConfig,
    spec: Optional[LMMeshSpec] = None,
    *,
    prompt_len: int,
    max_new: int,
    batch: int = 1,
    temperature: float = 0.0,
    top_k: int | None = None,
    devices=None,
    mesh=None,
    max_len: int | None = None,
    rolling: bool | None = None,
    kv_quant: bool = False,
    obs=None,
):
    """Build a jitted ``generate(params, prompt, rng) -> tokens`` function.

    ``prompt`` is (B, prompt_len) int32; the result is (B, max_new) int32.
    ``temperature=0`` decodes greedily; otherwise tokens are sampled from
    ``softmax(logits / temperature)``, optionally restricted to the
    ``top_k`` most likely tokens.  One XLA program: prefill + a
    ``lax.scan`` of single-token steps over a static-size KV cache.

    ``spec``/``devices`` (or an explicit ``mesh``) place the computation:
    batch over ``data``, attention heads over ``model`` (tensor-parallel
    decode), and the KV cache's sequence dimension over ``seq`` —
    context-parallel serving for prompts/caches one device cannot hold;
    the same logical-axis rules as training shard the cache, and GSPMD
    inserts the gather/reduce for the softmax over the sharded sequence
    (token-exact vs single device,
    ``tests/test_decode.py::test_seq_sharded_decode_matches_single_device``).
    ``cfg.attn_impl`` is ignored here — incremental decode is always
    cached dense attention; ring/Ulysses are training-time strategies
    for long-context *processing*.

    ``max_len`` overrides the KV-cache capacity (default
    ``prompt_len + max_new``).  Without a window every decode step reads
    the whole allocated buffer (masked), so per-step cost is set by the
    *capacity*, not the position — benchmarks comparing different
    ``max_new`` values must pin ``max_len`` to compare like with like.

    ``rolling`` selects the O(window)-memory ring cache (None = auto: on
    whenever ``cfg.attn_window`` is set and smaller than the cache
    length).  Windowed decode then allocates ``attn_window`` cache rows
    instead of ``max_len`` — identical outputs, ring-slot writes.

    ``kv_quant=True`` stores the KV cache int8 with per-(token, head)
    scales (``ops/quant.py``) — ~0.53x the cache bytes and HBM read
    traffic of bf16, the dominant decode cost at large batch.  Composes
    with GQA, sliding window and the rolling ring cache.  For int8
    *weights* too, pass ``ops.quant.quantize_lm_params(params)`` as the
    params — no generator flag needed (the matmul modules sniff the
    quantized tree).

    ``obs`` (an ``obs.events.EventWriter``) turns on per-request
    telemetry: each ``run()`` emits a ``decode_request`` span with
    ``dispatch``/``wait`` child spans and one ``decode`` event carrying
    prompt/output lengths, total latency, queueing delay,
    time-to-first-token, and tokens/s — the per-request fields
    ``obs summarize`` folds into serving-side p50/p95/p99
    (``obs/serving.py``).  Without obs, prefill and the per-token scan
    are ONE fused XLA program (no per-token dispatch from Python); with
    obs the program is split at the first sampled token — prefill+first
    token, then the remaining scan — so TTFT is a real fence on the
    first token rather than an estimate.  The split is sampling-exact
    (same RNG split sequence), costs one extra dispatch per request, and
    the second program is dispatched before the first is fenced, so the
    device pipeline stays full.  The fences make the request
    synchronous, which serving callers are anyway.

    ``run(..., submitted_at=perf_counter_value)`` lets a serving harness
    timestamp enqueue: the gap to dispatch is emitted as ``queue_delay``
    (0.0 for callers that dispatch inline).
    """
    refuse_cache_over_layer_types(cfg)
    if max_len is None:
        max_len = prompt_len + max_new
    elif max_len < prompt_len + max_new:
        raise ValueError(
            f"max_len {max_len} < prompt_len + max_new "
            f"({prompt_len} + {max_new})"
        )
    if rolling is None:
        rolling = bool(cfg.attn_window) and cfg.attn_window < max_len
    if rolling and not cfg.attn_window:
        raise ValueError("rolling=True requires cfg.attn_window > 0")
    if not cfg.causal:
        raise ValueError(
            "autoregressive decode requires a causal LM (cfg.causal=True); "
            "bidirectional-encoder configs (e.g. ViT's) have no decode order"
        )
    if top_k is not None:
        if temperature == 0.0:
            raise ValueError(
                "top_k has no effect with temperature=0 (greedy decoding); "
                "set a temperature or drop top_k"
            )
        if not 1 <= top_k <= cfg.vocab_size:
            raise ValueError(
                f"top_k {top_k} out of range [1, vocab_size={cfg.vocab_size}]"
            )
    validate_kv_head_sharding(cfg, spec or LMMeshSpec())
    if mesh is None:
        mesh = build_lm_mesh(spec or LMMeshSpec(), devices)
    rules = lm_logical_rules(cfg.fsdp)
    model = LMDecode(
        cfg, attn_core=prefill_attn_core(cfg, mesh, prompt_len)
    )
    sample = partial(sample_token, temperature=temperature, top_k=top_k)

    def make_step(params):
        def step(carry, _):
            last, caches, rng = carry
            rng, sub = jax.random.split(rng)
            tok = sample(last, sub)
            with nn.logical_axis_rules(rules):
                logits, caches = model.apply(
                    {"params": params}, tok[:, None], caches
                )
            return (logits[:, 0], caches, rng), tok

        return step

    def _prefill(params, prompt, rng):
        """Prompt forward + the FIRST sampled token applied to the cache
        — everything TTFT covers."""
        caches = init_kv_cache(
            cfg, batch, max_len, rolling=rolling, quant=kv_quant
        )
        with nn.logical_axis_rules(rules):
            logits, caches = model.apply(
                {"params": params}, prompt, caches, last_only=True
            )
        last = logits[:, -1]
        (last, caches, rng), tok0 = make_step(params)((last, caches, rng), None)
        return tok0, last, caches, rng

    def _rest(params, tok0, last, caches, rng):
        """Decode steps 1..max_new-1 — the same RNG split sequence as
        one fused prefill+scan program, so the two-program split is
        token-identical to the fused path."""
        (_, _, _), toks = lax.scan(
            make_step(params), (last, caches, rng), None, length=max_new - 1
        )
        return jnp.concatenate([tok0[:, None], toks.T], axis=1)

    def generate(params, prompt, rng):
        tok0, last, caches, rng = _prefill(params, prompt, rng)
        return _rest(params, tok0, last, caches, rng)

    tok_sharding = NamedSharding(mesh, DECODE_TOKEN_SPEC)

    jitted = jax.jit(
        generate,
        in_shardings=(None, tok_sharding, None),
        out_shardings=tok_sharding,
    )
    # the TTFT-splittable pair, compiled only when obs telemetry runs
    jitted_prefill = jax.jit(
        _prefill,
        in_shardings=(None, tok_sharding, None),
    )
    jitted_rest = jax.jit(_rest, out_shardings=tok_sharding)

    warmed = False
    # native request tracing (obs/trace.py span model): the one-shot
    # path emits the same trace_span chain the serve engine does —
    # request root, queue (when the caller timestamps enqueue), prefill
    # (dispatch -> first token), decode (the tail) — so `obs trace
    # --request/--slowest-request` works outside the serve engine.
    # Request ids are deterministic per generator (run id + sequence);
    # DDL_OBS_TRACE_SAMPLE=N thins to 1-in-N by sequence number, same
    # contract as ServeEngine(trace_sample=)
    seq = 0
    try:
        trace_sample = max(
            1, int(os.environ.get("DDL_OBS_TRACE_SAMPLE") or 1)
        )
    except ValueError:
        trace_sample = 1

    def _trace_span(name, t0_pc, t1_pc, *, trace, span, parent, **args):
        import time as _time

        wall, pc = _time.time(), _time.perf_counter()
        obs.emit(
            "trace_span", trace=trace, span=span, parent=parent,
            name=name, cat="decode",
            t0=wall - (pc - t0_pc), t1=wall - (pc - t1_pc), **args,
        )

    def run(params, prompt, rng=None, submitted_at=None):
        nonlocal warmed, seq
        if rng is None:
            rng = jax.random.key(0)
        if obs is None:
            with jax.set_mesh(mesh):
                return jitted(params, prompt, rng)
        from time import perf_counter

        from ddl_tpu.utils.timing import fence

        # the first request pays the XLA compile; flag it so summaries
        # can exclude it from steady-state percentiles (the same warmup
        # discipline as bench/analysis.comm_time_summary)
        warm, warmed = warmed, True
        req_id = f"{obs.run_id[:8]}-d{seq}"
        traced = seq % trace_sample == 0
        seq += 1
        t0 = perf_counter()
        # queueing delay: enqueue -> dispatch, when the serving harness
        # timestamps enqueue (perf_counter base); inline callers have no
        # queue, which 0.0 states honestly
        queue_delay = (
            max(0.0, t0 - submitted_at) if submitted_at is not None else 0.0
        )
        with obs.span(
            "decode_request", prompt_len=prompt_len, max_new=max_new,
            batch=batch,
        ):
            with obs.span("dispatch"):
                with jax.set_mesh(mesh):
                    # both programs dispatch back to back — the tail is
                    # queued behind prefill on the device, so fencing the
                    # first token below doesn't drain the pipeline
                    tok0, last, caches, rng2 = jitted_prefill(
                        params, prompt, rng
                    )
                    toks = jitted_rest(params, tok0, last, caches, rng2)
            with obs.span("wait"):
                with obs.span("first_token"):
                    fence(tok0)
                ttft = perf_counter() - t0
                fence(toks)
        dur = perf_counter() - t0
        if traced:
            end = perf_counter()
            first_tok = t0 + ttft
            root_t0 = submitted_at if submitted_at is not None else t0
            _trace_span(
                "request", root_t0, end,
                trace=req_id, span=f"{req_id}/req", parent=None,
                request_id=req_id, prompt_len=prompt_len,
                new_tokens=max_new, outcome="ok", dispatches=1,
            )
            if submitted_at is not None and submitted_at < t0:
                _trace_span(
                    "queue", submitted_at, t0,
                    trace=req_id, span=f"{req_id}/queue",
                    parent=f"{req_id}/req", request_id=req_id,
                )
            _trace_span(
                "prefill", t0, first_tok,
                trace=req_id, span=f"{req_id}/prefill",
                parent=f"{req_id}/req", tokens=prompt_len,
            )
            _trace_span(
                "decode", first_tok, end,
                trace=req_id, span=f"{req_id}/d0",
                parent=f"{req_id}/req", dispatch=0,
                new_tokens=max_new,
            )
        obs.emit(
            "decode",
            request_id=req_id,
            prompt_len=prompt_len,
            new_tokens=max_new,
            batch=batch,
            dur=dur,
            queue_delay=queue_delay,
            ttft=ttft,
            tok_per_s=batch * max_new / dur if dur > 0 else None,
            decode_tok_per_s=(
                batch * (max_new - 1) / (dur - ttft)
                if max_new > 1 and dur > ttft else None
            ),
            warm=warm,
        )
        return toks

    # sharding contract + lowering handles for `ddl_tpu lint`
    # (analysis/contracts.py), derived from the decode rule table:
    # decode has no train state to donate, and serving replicas
    # intentionally hold full parameter copies when the mesh has no
    # model axis — replication is contractual
    run.contract = decode_rules().contract()
    run.jitted = jitted
    run.mesh = mesh
    # abstract generate() args for the compiled-IR probes
    # (analysis/hlolint.py): the generator bakes batch/prompt_len in, so
    # the probe asks the factory for the committed shapes
    run.probe_inputs = lambda: (
        jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32),
        jax.random.key(0),
    )
    return run
