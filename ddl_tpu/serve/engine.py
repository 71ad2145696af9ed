"""Continuous-batching serving engine over the paged KV pool.

Two XLA programs, generalizing the PR-5 token-exact prefill/decode split
(``infer/decode.py``):

* **prefill** (one per prompt-length bucket): the unmodified
  ``infer.decode.LMDecode`` causal forward over ONE prompt, the first
  token sampled in-program (what TTFT covers), and the prompt's K/V
  scattered from its contiguous prefill cache into the request's pool
  blocks (``kv_pool.pool_write_prefill``).  Prompts are right-padded to
  power-of-two multiples of the block size — causal attention makes
  right-padding exact (pad rows influence nothing before them), and the
  bucket bound keeps recompiles logarithmic in prompt length.
* **decode** (one program per small bucket grid): K tokens for EVERY
  active lane in one dispatch — a ``lax.scan`` of single-token steps,
  the continuous-batching twin of ``make_lm_generator``'s fused scan.
  Each step forwards the lanes' pending tokens through the same
  ``LMDecode`` stack, over one ``kv_pool.PagedKV`` cache a layer — the
  block every other path runs, so any training snapshot and any
  configuration the block can express serves as-is.  The cache writes
  each lane's K/V row into the pool at its block-table position AND
  appends it to the chunk's contiguous per-lane view (each lane's table
  is gathered ONCE per dispatch, not per layer per step), then attends
  that view with a per-lane length mask (``ops.quant.kv_attend``: the
  einsum path off TPU and on sharded meshes, the Pallas one-pass kernel
  with a per-lane bias row on a single TPU).  The batch shape is static
  (``max_batch`` lanes; idle lanes write to a dropped block id and are
  masked), so admitting or retiring requests never recompiles; the two
  shape knobs that DO vary are bucketed to powers of two — the chunk
  length K (capped by ``max_steps_per_dispatch`` and by the soonest
  lane completion, so retire/admit still happen on time) and the
  block-table width (the max active reservation rounded up, so short
  requests don't pay attention over the whole pool) — bounding the
  program count at ``log2(max_steps) * log2(max_blocks_per_seq)``.

* **chunk prefill** (round 17, one program per (chunk-bucket, view
  width, mode)): prompt rows computed against context already IN the
  pool — written by an earlier chunk of the same request, or by a
  different request entirely via the prefix cache
  (``kv_pool.PrefixIndex``: shared prompt prefixes are refcount-shared
  block-table entries, prefill starts at the first uncached token).
  Chunks interleave with decode dispatches in the scheduler loop, so a
  32k prompt cannot stall admission behind its prefill.

Token-exactness: per lane, the program sequence (prefill logits at the
true prompt end -> sample -> forward -> sample ...) is the same program
sequence ``make_lm_generator`` runs for a single request, over the same
attention math — the engine with N concurrent clients produces
bit-identical tokens to N sequential decodes
(tests/test_serve.py::test_engine_matches_sequential_decode), and the
prefix cache / chunked prefill change scheduling and footprint, never
tokens (tests/test_serve_prefix.py; the one documented exception is
int8 prefix REUSE, which attends the lossy stored rows — see
``ServeEngine.__init__``'s ``prefix_cache`` comment).

Sharding: lanes over ``data`` (the decode batch is the serving batch),
heads over ``model`` inside the program via the training rule table,
pool blocks over ``seq`` (the paged sequence dim) — validated by the
``serve_decode`` contract probe on a simulated mesh.
"""

from __future__ import annotations

import os
import time
from collections import deque, namedtuple
from functools import partial
from time import perf_counter
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ddl_tpu.infer.decode import (
    DECODE_TOKEN_SPEC,
    LMDecode,
    init_kv_cache,
    prefill_attn_core,
    sample_token,
)
from ddl_tpu.infer.kv_cache import ContiguousKV, decode_attention_path
from ddl_tpu.models.transformer import LMConfig, refuse_cache_over_layer_types
from ddl_tpu.ops.quant import QuantKV, kv_slice
from ddl_tpu.parallel.sharding import (
    LMMeshSpec,
    build_lm_mesh,
    lm_logical_rules,
    validate_kv_head_sharding,
)
from ddl_tpu.serve.admission import AdmissionController
from ddl_tpu.serve.kv_pool import (
    BlockAllocator,
    PagedKV,
    PrefixIndex,
    apply_block_permutation,
    blocks_for,
    init_kv_pool,
    pool_copy_block,
    pool_gather,
    pool_write_prefill,
)
from ddl_tpu.serve.scheduler import (
    ContinuousScheduler,
    Request,
    tenant_tags,
)

__all__ = [
    "ServeEngine", "make_serve_step_fns", "prompt_bucket", "pow2_at_most",
    "pow2_at_least",
]


def prompt_bucket(prompt_len: int, block_size: int) -> int:
    """Padded prompt length: the smallest power-of-two multiple of
    ``block_size`` at or above ``prompt_len`` — O(log) distinct prefill
    programs over any prompt-length distribution."""
    if prompt_len < 1:
        raise ValueError(f"prompt_len must be >= 1, got {prompt_len}")
    n = 1
    while n * block_size < prompt_len:
        n *= 2
    return n * block_size


def pow2_at_most(n: int) -> int:
    """Largest power of two <= n (n >= 1) — chunk lengths are floored to
    this so the decode-program grid stays logarithmic."""
    if n < 1:
        raise ValueError(f"need n >= 1, got {n}")
    return 1 << (n.bit_length() - 1)


def pow2_at_least(n: int) -> int:
    """Smallest power of two >= n (n >= 1) — block-table widths are
    rounded up to this, same reasoning."""
    if n < 1:
        raise ValueError(f"need n >= 1, got {n}")
    return 1 << (n - 1).bit_length()


ServeStepFns = namedtuple(
    "ServeStepFns",
    ["prefill_for", "chunk_for", "decode_for", "mesh", "contract", "cfg",
     "block_size", "num_blocks", "max_batch", "max_blocks_per_seq",
     "kv_quant", "init_pools", "probe_inputs"],
)

# Minimum gathered-view rows for the CHUNK prefill programs (Tq > 1
# masked attention over a pool view).  Empirically (probed on this
# runtime, pinned by the bit-identity e2es): masked cached attention
# reproduces the fused causal prefill bit-for-bit at every probed view
# width >= 64 rows, while 16/32-row views drift at ~1e-6 — enough to
# flip a near-tie argmax.  Chunk programs therefore gather at least
# this many rows; single-token decode (Tq == 1) is bit-stable at every
# width and keeps its tight view.
MIN_CHUNK_VIEW_ROWS = 64


def make_serve_step_fns(
    cfg: LMConfig,
    spec: Optional[LMMeshSpec] = None,
    *,
    block_size: int,
    num_blocks: int,
    max_batch: int,
    max_blocks_per_seq: int | None = None,
    temperature: float = 0.0,
    top_k: int | None = None,
    kv_quant: bool = False,
    devices=None,
    mesh=None,
):
    """Build the serving engine's two jitted programs.

    Returns a ``ServeStepFns``: ``prefill_for(bucket_len)`` lazily
    builds/caches the per-bucket prefill program; ``decode_for(k, nmax)``
    the K-step continuous-batch chunk over (B, nmax) block tables.
    ``.contract`` declares the jit boundary for the sharding-contract
    probes (``analysis/contracts.py`` ``serve_decode``)."""
    spec = spec or LMMeshSpec()
    if not cfg.causal:
        raise ValueError("serving decode requires a causal LM")
    refuse_cache_over_layer_types(cfg)
    if spec.pipe > 1 or spec.expert > 1:
        raise ValueError(
            "serving meshes use data/seq/model axes only (pipe/expert "
            f"must be 1, got pipe={spec.pipe} expert={spec.expert}); "
            "pipelined/expert-parallel serving is a scheduler change, "
            "not a mesh flag"
        )
    if top_k is not None and temperature == 0.0:
        raise ValueError(
            "top_k has no effect with temperature=0 (greedy decoding)"
        )
    validate_kv_head_sharding(cfg, spec)
    if mesh is None:
        mesh = build_lm_mesh(spec, devices)
    if max_blocks_per_seq is None:
        max_blocks_per_seq = num_blocks
    if max_blocks_per_seq > num_blocks:
        raise ValueError(
            f"max_blocks_per_seq {max_blocks_per_seq} > pool size "
            f"{num_blocks}"
        )
    rules = lm_logical_rules(cfg.fsdp)

    # (V,) logits -> sampled token: the generator's own sampling, a lane
    sample_one = partial(sample_token, temperature=temperature, top_k=top_k)

    model = LMDecode(cfg)

    def _decode_chunk(params, pools, tables, lengths, pending, rngs, *, k):
        """K fused single-token steps for every lane — same per-step
        program (and RNG split sequence) as one step at a time, one
        dispatch.  Each lane's block table is gathered into a contiguous
        per-lane cache ONCE here; the scan appends rows to that view (a
        (B, fused) scatter) instead of re-gathering (B, L, fused) per
        layer per step.  Returns toks (K, B)."""
        views = tuple(pool_gather(p, tables) for p in pools)

        def body(carry, _):
            pools, views, lengths, pending, rngs = carry
            caches = tuple(
                PagedKV(p, c, tables, lengths) for p, c in zip(pools, views)
            )
            with nn.logical_axis_rules(rules):
                logits, caches = model.apply(
                    {"params": params}, pending[:, None], caches
                )
            last = logits[:, 0]  # (B, V) f32
            pair = jax.vmap(jax.random.split)(rngs)  # (B, 2, key)
            new_rngs, subs = pair[:, 0], pair[:, 1]
            toks = jax.vmap(sample_one)(last, subs)
            pools = tuple(c.pool for c in caches)
            views = tuple(c.view for c in caches)
            return (pools, views, caches[0].lengths, toks, new_rngs), toks

        (pools, _, _, _, rngs), toks = lax.scan(
            body, (pools, views, lengths, pending, rngs), None, length=k
        )
        return toks, rngs, pools

    tok_sharding = NamedSharding(mesh, DECODE_TOKEN_SPEC)
    _decode_cache: dict[tuple[int, int], object] = {}

    def decode_for(k: int, nmax: int):
        """The jitted K-step decode program over (B, nmax)-wide block
        tables; ``(program, newly_built)``.  Callers pass power-of-two
        ``k``/``nmax`` so the grid stays ``log2 x log2``."""
        prog = _decode_cache.get((k, nmax))
        if prog is not None:
            return prog, False
        prog = jax.jit(
            partial(_decode_chunk, k=k),
            in_shardings=(None, None, None, None, tok_sharding, None),
            out_shardings=(None, None, None),
        )
        _decode_cache[k, nmax] = prog
        return prog, True

    _prefill_cache: dict[int, object] = {}

    def prefill_for(bucket_len: int):
        """The jitted prefill+first-token program for one prompt-length
        bucket: ``(params, pools, prompt (1, Pb), block_ids, true_len,
        rng) -> (tok0, new_rng, pools)``."""
        if bucket_len % block_size:
            raise ValueError(
                f"bucket {bucket_len} must be a multiple of "
                f"block_size {block_size}"
            )
        prog = _prefill_cache.get(bucket_len)
        if prog is not None:
            return prog
        pre_model = LMDecode(
            cfg, attn_core=prefill_attn_core(cfg, mesh, bucket_len)
        )

        def _prefill(params, pools, prompt, block_ids, true_len, rng):
            caches = init_kv_cache(cfg, 1, bucket_len, quant=kv_quant)
            with nn.logical_axis_rules(rules):
                logits, caches = pre_model.apply(
                    {"params": params}, prompt, caches,
                    last_index=true_len - 1,
                )
            # logits at the TRUE prompt end — right-pad rows beyond it
            # are causally invisible, and last_index slices BEFORE the
            # final norm+head so the head runs on the same (1, 1, D)
            # shape as the generator's last_only prefill: bit-identical
            # next-token logits despite the bucket padding
            last = logits[0, 0]
            rng, sub = jax.random.split(rng)
            tok0 = sample_one(last, sub)
            pools = tuple(
                pool_write_prefill(pools[i], caches[i].kv, block_ids)
                for i in range(cfg.n_layers)
            )
            return tok0, rng, pools

        prog = jax.jit(_prefill)
        _prefill_cache[bucket_len] = prog
        return prog

    _chunk_cache: dict[tuple[int, int, str], object] = {}

    def chunk_for(cb: int, nmax: int, mode: str = "final"):
        """The jitted CHUNK prefill program over one request's block
        table: ``(params, pools, tokens (1, cb), table (nmax,), off,
        last_index, rng)`` computes prompt rows [off, off+cb) against
        the already-written context [0, off) gathered from the pool —
        the continuation of a prefill another program (or another
        REQUEST, via the prefix cache) started.

        ``off`` is traced, which routes ``LMDecode`` through its
        masked cached-attention branch (positions/mask derive from the
        offset); probed bit-identical to the fused offset-0 prefill at
        every view width >= ``MIN_CHUNK_VIEW_ROWS``.  Chunk starts are
        ALWAYS block-aligned (a fully-cached prompt re-prefills its
        whole last block, through copy-on-write, rather than running an
        unaligned single-row chunk).  Modes:

        * ``"mid"``    — intermediate chunk: scatters its rows into the
          pool blocks, logits discarded (head over one row).
        * ``"final"``  — last chunk: scatters rows AND samples the
          first token at ``last_index`` (same rng split sequence as the
          one-shot prefill), returning ``(tok0, rng, pools)``.

        ``(cb, nmax, mode)`` all ride power-of-two bucketing, so
        ``precompile`` still bounds the program set."""
        if mode not in ("mid", "final"):
            raise ValueError(f"unknown chunk mode {mode!r}")
        if cb % block_size:
            raise ValueError(
                f"chunk {cb} must be a multiple of block_size {block_size}"
            )
        prog = _chunk_cache.get((cb, nmax, mode))
        if prog is not None:
            return prog, False

        def _chunk(params, pools, tokens, table, off, last_index, rng):
            tables = table[None, :]
            caches = tuple(
                ContiguousKV(pool_gather(p, tables), off) for p in pools
            )
            with nn.logical_axis_rules(rules):
                logits, caches = model.apply(
                    {"params": params}, tokens, caches,
                    last_index=last_index if mode != "mid" else 0,
                )
            ids = lax.dynamic_slice(
                table, (off // block_size,), (cb // block_size,)
            )
            pools = tuple(
                pool_write_prefill(
                    pools[i], kv_slice(caches[i].kv, off, cb), ids
                )
                for i in range(cfg.n_layers)
            )
            if mode == "mid":
                return pools
            last = logits[0, 0]
            rng, sub = jax.random.split(rng)
            tok0 = sample_one(last, sub)
            return tok0, rng, pools

        prog = jax.jit(_chunk)
        _chunk_cache[cb, nmax, mode] = prog
        return prog, True

    contract = {
        "in_specs": {"pending": DECODE_TOKEN_SPEC},
        "donate_state": False,
        # serving replicas hold full parameter copies when the mesh has
        # no model axis — same waiver as the one-shot decode generator
        "replicated_params_ok": True,
    }

    def probe_inputs(kind, n):
        """Abstract per-program args (after params/pools) for the
        lowering probes (analysis/contracts.py, analysis/hlolint.py):
        ``("decode", k)`` matches ``decode_for(k, nmax)``, ``("prefill",
        bucket)`` matches ``prefill_for(bucket)``, ``("chunk", cb)``
        matches ``chunk_for(cb, nmax, mode)`` — the engine owns these
        shapes, so the probes can't drift from the real call sites."""
        i32 = jnp.int32
        nmax = max_blocks_per_seq
        key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        if kind == "decode":
            return (
                jax.ShapeDtypeStruct((n, nmax), i32),
                jax.ShapeDtypeStruct((n,), i32),
                jax.ShapeDtypeStruct((n,), i32),
                jax.ShapeDtypeStruct((n, 2), jnp.uint32),
            )
        if kind == "prefill":
            return (
                jax.ShapeDtypeStruct((1, n), i32),
                jax.ShapeDtypeStruct((1,), i32),
                jax.ShapeDtypeStruct((), i32),
                key,
            )
        if kind == "chunk":
            return (
                jax.ShapeDtypeStruct((1, n), i32),
                jax.ShapeDtypeStruct((nmax,), i32),
                jax.ShapeDtypeStruct((), i32),
                jax.ShapeDtypeStruct((), i32),
                key,
            )
        raise ValueError(f"unknown probe kind {kind!r}")

    return ServeStepFns(
        prefill_for=prefill_for, chunk_for=chunk_for,
        decode_for=decode_for, mesh=mesh,
        contract=contract, cfg=cfg, block_size=block_size,
        num_blocks=num_blocks, max_batch=max_batch,
        max_blocks_per_seq=max_blocks_per_seq, kv_quant=kv_quant,
        init_pools=lambda: init_kv_pool(
            cfg, num_blocks, block_size, quant=kv_quant
        ),
        probe_inputs=probe_inputs,
    )


def _jit_compiles(prog) -> int:
    """How many executables this jitted program has compiled — the
    ground truth for cold-marking (a program compiles once per operand
    signature, not once per shape: the same program compiles AGAIN when
    its pools go from fresh to committed, or when an operand was made
    another way — see ``ServeEngine._args``).  Leans on the private
    ``PjitFunction._cache_size`` of the one supported installation;
    ``tests/test_serve.py`` pins that it exists."""
    return prog._cache_size()


class ServeEngine:
    """The serving loop: admission queue -> continuous decode batch.

    ``submit()`` enqueues prompts (admission control may shed);
    ``step()`` runs one scheduler iteration (retire, admit+prefill, one
    batched decode step); ``run()`` loops until drained and returns
    ``{request_id: np.ndarray of sampled tokens}``.  Per-request
    ``decode`` obs events (duration, queue delay, a fenced TTFT,
    tokens/s) flow into the same ``obs summarize`` percentiles as the
    one-shot path, plus ``serve_admit``/``serve_retire``/``serve_shed``/
    ``kv_pool_stats`` engine events."""

    def __init__(
        self,
        cfg: LMConfig,
        params,
        spec: Optional[LMMeshSpec] = None,
        *,
        block_size: int = 16,
        num_blocks: int = 64,
        max_batch: int = 8,
        max_blocks_per_seq: int | None = None,
        temperature: float = 0.0,
        top_k: int | None = None,
        kv_quant: bool = False,
        max_queue: int = 64,
        policy: str = "reject",
        min_free_blocks: int = 0,
        max_steps_per_dispatch: int = 8,
        defrag_threshold: float | None = None,
        prefix_cache: bool | str = "auto",
        prefill_chunk: int | None = None,
        scenario: str | None = None,
        trace_sample: int | None = None,
        obs=None,
        trace_requests: bool = True,
        guard=None,
        devices=None,
        mesh=None,
    ) -> None:
        self.fns = make_serve_step_fns(
            cfg, spec, block_size=block_size, num_blocks=num_blocks,
            max_batch=max_batch, max_blocks_per_seq=max_blocks_per_seq,
            temperature=temperature, top_k=top_k, kv_quant=kv_quant,
            devices=devices, mesh=mesh,
        )
        self.cfg = cfg
        self.params = params
        self.obs = obs
        # per-request causal tracing (obs/trace.py): every request emits
        # a root span plus queue/prefill/decode-dispatch children into
        # the obs stream, so `obs trace <job> --request ID` reconstructs
        # that one request's timeline.  A handful of events per request
        # on top of the decode/serve_* kinds; operators running at
        # volumes where that matters turn it off here, or keep 1-in-N
        # via ``trace_sample`` (default: DDL_OBS_TRACE_SAMPLE, else
        # every request) — deterministic by request sequence number, so
        # a re-run samples the same requests.
        self.trace_requests = bool(trace_requests)
        if trace_sample is None:
            try:
                trace_sample = int(
                    os.environ.get("DDL_OBS_TRACE_SAMPLE") or 1
                )
            except ValueError:
                trace_sample = 1
        self.trace_sample = max(1, int(trace_sample))
        self.defrag_threshold = defrag_threshold
        # prefix caching: "auto" enables it for lossless (non-int8)
        # pools only.  An int8 pool stores K/V lossily, so a reused
        # prefix is attended at quantization precision while a fresh
        # prefill attends the raw activations — prefix reuse there is
        # within int8 tolerance, not bit-identical, and must be an
        # explicit opt-in (documented in ARCHITECTURE.md).
        if prefix_cache == "auto":
            prefix_cache = not kv_quant
        self.prefix = PrefixIndex(block_size) if prefix_cache else None
        # chunked prefill: a prompt longer than this runs as multiple
        # bounded chunk programs interleaved with decode dispatches in
        # the scheduler loop, so one 32k prompt cannot stall admission.
        if prefill_chunk is not None:
            if (
                prefill_chunk < block_size
                or prompt_bucket(prefill_chunk, block_size) != prefill_chunk
            ):
                raise ValueError(
                    f"prefill_chunk must be a power-of-two multiple of "
                    f"block_size {block_size}, got {prefill_chunk}"
                )
        self.prefill_chunk = prefill_chunk
        self.scenario = scenario
        # made under the mesh like every program OUTPUT that replaces
        # them: an array created outside ``set_mesh`` has another type
        # (no mesh in its aval) than one a program returned under it, and
        # jit would compile each program a second time for the change
        with jax.set_mesh(self.fns.mesh):
            self.pools = self.fns.init_pools()
            self._rngs = jnp.zeros((max_batch, 2), jnp.uint32)
        self.allocator = BlockAllocator(num_blocks, block_size)
        # HBM ledger (obs/hbm.py): per-shard byte sizes, computed once
        # on first pool-stats emission.  The pool's logical footprint
        # divides exactly into num_blocks, so the allocator's block
        # counts (cached/used/free partition the pool) convert to bytes
        # without rounding.
        self._hbm_block_bytes: int | None = None
        self._hbm_params_bytes: int | None = None
        if self.prefix is not None:
            self.allocator.on_evict = self.prefix.forget_block
        self.scheduler = ContinuousScheduler(
            self.allocator, max_batch, self.fns.max_blocks_per_seq,
            min_free_blocks=min_free_blocks, prefix_index=self.prefix,
        )
        self.admission = AdmissionController(
            max_queue=max_queue, policy=policy, obs=obs,
            on_shed=self._record_shed, trace=self.trace_requests,
        )
        if max_steps_per_dispatch < 1:
            raise ValueError(
                f"max_steps_per_dispatch must be >= 1, got "
                f"{max_steps_per_dispatch}"
            )
        self.max_steps_per_dispatch = int(max_steps_per_dispatch)
        self.results: dict[str, np.ndarray] = {}
        self.outcomes: dict[str, str] = {}  # id -> ok | shed:<reason>
        # per-request decode records (same fields as the emitted events),
        # so ServingStats percentiles work without an EventWriter too.
        # Bounded: a long-running server keeps the newest window (the
        # durable stream is the EventWriter); results/outcomes are the
        # caller's to drain via pop_result() — a server that never pops
        # grows by one token array per request forever
        self.request_log: deque = deque(maxlen=65536)
        self._req_counter = 0
        self._cow_prog = None  # lazily-jitted pool_copy_block
        self.stats = {
            "submitted": 0, "completed": 0, "shed": 0,
            "prefill_compiles": 0, "decode_compiles": 0,
            "decode_steps": 0, "decode_dispatches": 0, "peak_blocks": 0,
            "prefix_hits": 0, "prefix_hit_tokens": 0, "prefix_inserts": 0,
            "prefill_tokens": 0, "prefill_chunks": 0, "cow_copies": 0,
            "decode_attention": decode_attention_path(self.fns.mesh.size),
        }
        self._compiled_buckets: set[int] = set()
        # preempt-drain: ``guard`` is a utils/preemption.PreemptionGuard
        # (or anything with ``.requested``) polled at every step() — the
        # supervisor's SIGTERM flips it, and the engine answers by
        # draining (admission closed, queued requests shed tenant-
        # tagged, in-flight lanes finishing) instead of dying
        # mid-dispatch.  None = drain only on an explicit drain() call.
        self.guard = guard
        self.draining = False
        self.drain_reason: str | None = None
        # parked-request resume state (round 24): drain(park=True)
        # records, per unfinished lane, everything resume_parked()
        # needs to complete the stream exactly — the original request,
        # its partial outputs, and the lane's rng carry at park time
        self.parked: dict[str, dict] = {}

    # -- submission -------------------------------------------------------
    def submit(
        self, prompt, max_new: int, request_id: str | None = None,
        submitted_at: float | None = None, rng_seed: int = 0,
        tenant: str | None = None, priority_class: str | None = None,
    ) -> str:
        """Offer one prompt; returns its admission outcome (see
        ``AdmissionController.offer``).  ``tenant``/``priority_class``
        tag every event the request emits (admit/shed/retire/decode/
        trace spans) for per-tenant SLO attribution; untagged requests
        fold into the ``"default"`` tenant downstream."""
        if request_id is None:
            request_id = f"r{self._req_counter:05d}"
        seq = self._req_counter
        self._req_counter += 1
        req = Request(
            id=request_id,
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new=int(max_new),
            submitted_at=(
                perf_counter() if submitted_at is None else submitted_at
            ),
            rng_seed=rng_seed,
            # 1-in-N trace sampling, deterministic by request sequence
            # number (NOT an RNG draw): request k is traced iff
            # k % trace_sample == 0, so re-runs and replays sample the
            # same requests and `obs trace --slowest-request` selects
            # over a stable subset
            traced=self.trace_requests and seq % self.trace_sample == 0,
            tenant=str(tenant) if tenant else None,
            priority_class=str(priority_class) if priority_class else None,
        )
        self.stats["submitted"] += 1
        if self.draining:
            # admission is closed: shed at the door (tenant-tagged, so
            # the per-class SLO accounting sees WHO the drain cost)
            self.admission.shed_request(req, "draining")
            outcome = "rejected"
        else:
            outcome = self.admission.offer(
                req, fits_ever=self.scheduler.fits_ever(req)
            )
        if outcome == "rejected":
            self.stats["shed"] += 1
        return outcome

    def _record_shed(self, req: Request, reason: str) -> None:
        self.outcomes[req.id] = f"shed:{reason}"
        if reason == "queue_full" and self.admission.policy == "shed_oldest":
            self.stats["shed"] += 1

    @property
    def busy(self) -> bool:
        return bool(self.scheduler.active()) or bool(self.admission.queue)

    # -- engine iteration -------------------------------------------------
    def _emit_trace_span(
        self, name: str, t0_pc: float, t1_pc: float, *,
        trace: str, span: str, parent: str | None, traced: bool = True,
        **args,
    ) -> None:
        """One completed causal span into the obs stream.  Engine timing
        runs on ``perf_counter``; trace consumers need wall clock (spans
        merge across hosts through the clock-offset fit), so both stamps
        are mapped through the current (wall, perf_counter) pair.
        ``traced`` carries the request's 1-in-N sampling decision."""
        if self.obs is None or not self.trace_requests or not traced:
            return
        wall, pc = time.time(), perf_counter()
        self.obs.emit(
            "trace_span", trace=trace, span=span, parent=parent,
            name=name, cat="serve",
            t0=wall - (pc - t0_pc), t1=wall - (pc - t1_pc), **args,
        )

    def _emit_pool_stats(self, **extra) -> None:
        if self.obs is not None:
            stats = self.allocator.stats()
            self.obs.emit(
                "kv_pool_stats",
                **stats,
                queue_depth=len(self.admission),
                active_lanes=len(self.scheduler.active()),
                **extra,
            )
            self._emit_hbm_sample(stats)

    def _emit_hbm_sample(self, stats: dict) -> None:
        """HBM ledger: one ``hbm_sample`` per pool-stats emission, with
        the KV pool split by what the allocator knows — ``kv_private``
        (lane-owned, refcount >= 1), ``kv_cached`` (refcount-0 prefix
        blocks kept for reuse), ``kv_free`` (headroom).  The three
        partition the pool, so their sum is the pool's full footprint
        regardless of churn."""
        from ddl_tpu.obs import hbm

        if self._hbm_block_bytes is None:
            pool_bytes = hbm.tree_shard_bytes(self.pools) or 0
            self._hbm_block_bytes = pool_bytes // max(1, self.fns.num_blocks)
            self._hbm_params_bytes = hbm.tree_shard_bytes(self.params)
        bb = self._hbm_block_bytes
        hbm.live_sample(
            self.obs,
            params_bytes=self._hbm_params_bytes,
            kv_cached_bytes=stats["cached"] * bb,
            kv_private_bytes=stats["used"] * bb,
            kv_free_bytes=stats["free"] * bb,
            context="serve",
        )

    def _emit_hbm_plan(self, label: str, prog, args: tuple) -> None:
        """Stamp one ``hbm_plan`` static budget for a serving program
        that just compiled (the caller's compile detection already
        fired, so emission frequency == compile frequency).  Runs under
        the serving mesh because ``lower()`` re-traces the program —
        DDL_HBM_PLAN=off|aval dials the cost down (obs/hbm.py)."""
        if self.obs is None:
            return
        mode = os.environ.get("DDL_HBM_PLAN", "").strip().lower()
        if mode in ("0", "off", "false"):
            return
        from ddl_tpu.obs import hbm

        with jax.set_mesh(self.fns.mesh):
            hbm.plan_program(
                self.obs, label, prog, args,
                mode="aval" if mode == "aval" else "full",
            )

    def _retire_finished(self) -> None:
        for state in self.scheduler.finished():
            self.scheduler.retire(state.lane)
            req = state.request
            toks = state.outputs
            if req.resume_prefix is not None:
                # resumed request: the client stream is the tokens
                # generated BEFORE the park plus this incarnation's —
                # token-identical to an uninterrupted decode (the
                # prefix was re-prefilled as prompt, the rng carry
                # restored, so the continuation is the same draw)
                toks = list(req.resume_prefix) + list(state.outputs)
            self.results[req.id] = np.asarray(toks, np.int32)
            self.outcomes[req.id] = "ok"
            self.stats["completed"] += 1
            end = state.finished_at or perf_counter()
            dur = max(end - state.admitted_at, 1e-9)
            queue_delay = (
                max(0.0, state.admitted_at - req.submitted_at)
                if req.submitted_at is not None else 0.0
            )
            record = dict(
                request_id=req.id,
                prompt_len=req.prompt_len,
                new_tokens=len(state.outputs),
                batch=1,
                dur=dur,
                queue_delay=queue_delay,
                ttft=state.ttft_s,
                tok_per_s=len(state.outputs) / dur,
                warm=not state.cold,
                chips=self.fns.mesh.size,
                engine="serve",
                **tenant_tags(req),
            )
            self.request_log.append(
                {"kind": "decode", "ts": time.time(), **record}
            )
            # the trace ROOT: submit -> retire, parent of the queue/
            # prefill/decode spans emitted along the way
            self._emit_trace_span(
                "request",
                (
                    req.submitted_at if req.submitted_at is not None
                    else state.admitted_at
                ),
                end,
                trace=req.id, span=f"{req.id}/req", parent=None,
                traced=req.traced,
                request_id=req.id, lane=state.lane,
                prompt_len=req.prompt_len, new_tokens=len(state.outputs),
                dispatches=len(state.dispatches), outcome="ok",
                cached_tokens=state.cached_tokens,
                **tenant_tags(req),
            )
            if self.obs is not None:
                self.obs.emit("decode", **record)
                self.obs.emit(
                    "serve_retire",
                    request_id=req.id,
                    lane=state.lane,
                    new_tokens=len(state.outputs),
                    dur=dur,
                    freed_blocks=len(state.block_ids),
                    **tenant_tags(req),
                )
                self._emit_pool_stats()

    def _admit_one(
        self, req: Request, shared: list[int] | None = None
    ) -> None:
        state = self.scheduler.try_admit(req, shared)
        assert state is not None  # caller checked can_admit
        fns = self.fns
        t0 = perf_counter()
        state.admitted_at = t0
        # the pool peak is set at ADMISSION (the reservation just
        # happened) — a chunked lane's _finish_prefill runs many steps
        # later, by which time co-resident lanes may have retired
        self.stats["peak_blocks"] = max(
            self.stats["peak_blocks"], self.allocator.used_blocks
        )
        if state.cached_tokens:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += state.cached_tokens
            if self.obs is not None:
                self.obs.emit(
                    "prefix_hit",
                    request_id=req.id,
                    cached_tokens=state.cached_tokens,
                    blocks=state.shared_blocks,
                    prompt_len=req.prompt_len,
                )
        # chunked prefill engages when the prompt continues a cached
        # prefix (start at the first uncached token) or exceeds the
        # chunk bound; otherwise the original single-program bucketed
        # prefill runs inline — byte-identical program sequence to the
        # pre-prefix-cache engine
        chunked = state.prefill_pos > 0 or (
            self.prefill_chunk is not None
            and req.prompt_len > self.prefill_chunk
        )
        if not chunked:
            self._full_prefill(state, t0)
        # chunk programs run one per scheduler iteration
        # (_advance_prefill), interleaved with decode dispatches, so a
        # long prompt never monopolizes the loop
        if req.submitted_at is not None and req.submitted_at < t0:
            self._emit_trace_span(
                "queue", req.submitted_at, t0,
                trace=req.id, span=f"{req.id}/queue",
                parent=f"{req.id}/req", traced=req.traced,
                request_id=req.id,
                **tenant_tags(req),
            )
        if self.obs is not None:
            self.obs.emit(
                "serve_admit",
                request_id=req.id,
                lane=state.lane,
                bucket=prompt_bucket(req.prompt_len, fns.block_size),
                prompt_len=req.prompt_len,
                max_new=req.max_new,
                blocks=len(state.block_ids),
                cached_tokens=state.cached_tokens,
                prefill_tokens=req.prompt_len - state.cached_tokens,
                queue_delay=(
                    max(0.0, t0 - req.submitted_at)
                    if req.submitted_at is not None else 0.0
                ),
                # for an inline full prefill this is ITS compile flag;
                # a chunked admission hasn't run any program yet, so
                # chunked=True tells consumers to read per-chunk
                # compile flags off the prefill trace spans instead
                compiled=state.cold,
                chunked=chunked,
                **({"scenario": self.scenario} if self.scenario else {}),
                **tenant_tags(req),
            )
            self._emit_pool_stats()

    def _args(self, *operands) -> tuple:
        """The full argument tuple of a serving program: params, pools,
        then the per-call operands.  Host operands arrive as numpy
        arrays/scalars and are converted HERE, for the live loop and for
        ``precompile()``'s dummies alike: jit keys its cache on how an
        operand was made, not only on its shape (an array from a
        ``jnp`` creation op under ``set_mesh`` carries the mesh in its
        type, a converted numpy array does not), so a dummy made another
        way leaves one compile inside the first live request."""
        return (self.params, self.pools, *map(jnp.asarray, operands))

    def _dispatch(self, prog, *operands):
        with jax.set_mesh(self.fns.mesh):
            return prog(*self._args(*operands))

    def _prefill_rng(self, req: Request):
        """The rng a prefill program seeds its lane with.  An ordinary
        request derives it from ``rng_seed``; a resumed one restores the
        parked lane's CARRY — prefill's split discipline matches the
        decode scan body's (carry in, ``(carry', sub)`` out), so
        re-prefilling prompt+partial-outputs with the recorded carry
        produces exactly the token the interrupted decode would have
        sampled next, and every token after it."""
        if req.resume_rng is not None:
            return jnp.asarray(req.resume_rng, jnp.uint32)
        return jax.random.PRNGKey(req.rng_seed)

    def _full_prefill(self, state, t0: float) -> None:
        """The original whole-prompt bucketed prefill, run inline at
        admission (short prompts with no cached prefix)."""
        req = state.request
        fns = self.fns
        bucket = prompt_bucket(req.prompt_len, fns.block_size)
        prog = fns.prefill_for(bucket)
        prompt = np.zeros((1, bucket), np.int32)
        prompt[0, : req.prompt_len] = req.prompt
        ids = np.full((bucket // fns.block_size,), fns.num_blocks, np.int32)
        n = min(len(ids), len(state.block_ids))
        ids[:n] = state.block_ids[:n]
        rng = self._prefill_rng(req)
        before = _jit_compiles(prog)
        operands = (prompt, ids, np.int32(req.prompt_len), rng)
        tok0, rng, self.pools = self._dispatch(prog, *operands)
        tok0 = int(tok0)  # fences the first token: a REAL TTFT
        # compile detection by executable count, not first-build: the
        # same program compiles AGAIN on its second call when the pools
        # go from fresh to committed (precompile's two-pass rationale) —
        # that hidden compile must cold-mark and count too
        compiled = _jit_compiles(prog) != before
        self._compiled_buckets.add(bucket)
        if compiled:
            self.stats["prefill_compiles"] += 1
            self._emit_hbm_plan(
                f"serve_prefill_b{bucket}", prog, self._args(*operands)
            )
        self.stats["prefill_tokens"] += req.prompt_len
        self._emit_trace_span(
            "prefill", t0, perf_counter(),
            trace=req.id, span=f"{req.id}/prefill",
            parent=f"{req.id}/req", traced=req.traced,
            request_id=req.id, lane=state.lane,
            bucket=bucket, compiled=compiled,
            **tenant_tags(req),
        )
        self._finish_prefill(state, tok0, rng, cold=compiled)

    def _finish_prefill(self, state, tok0: int, rng, cold: bool) -> None:
        """Common prefill completion: first token recorded (the TTFT
        fence already happened), rng parked in the lane slot, prompt
        blocks registered in the prefix index."""
        req = state.request
        state.ttft_s = perf_counter() - state.admitted_at
        state.pending_tok = tok0
        state.outputs.append(tok0)
        # cold (percentile-excluded) if any prefill program compiled; a
        # first-use decode program additionally cold-marks every lane in
        # that chunk (_decode_batch)
        state.cold = state.cold or cold
        state.prefill_done = True
        state.prefill_pos = req.prompt_len
        state.length = req.prompt_len
        if state.done:
            state.finished_at = perf_counter()
        self._rngs = self._rngs.at[state.lane].set(rng)
        self.stats["peak_blocks"] = max(
            self.stats["peak_blocks"], self.allocator.used_blocks
        )
        if self.prefix is not None:
            n = self.prefix.insert(
                req.prompt, state.block_ids, self.allocator,
                keys=req.chain_keys,
            )
            if n:
                self.stats["prefix_inserts"] += n
                if self.obs is not None:
                    self.obs.emit(
                        "prefix_insert",
                        request_id=req.id,
                        blocks=n,
                        tokens=n * self.fns.block_size,
                    )

    # -- chunked prefill --------------------------------------------------
    def _view_blocks(self, n_blocks: int) -> int:
        """Block-table width for a chunk program over an ``n_blocks``
        reservation: rounded up to a power of two, floored at
        MIN_CHUNK_VIEW_ROWS rows (the bit-identity clamp — see the
        constant's comment), capped by the engine envelope.  The ONE
        width formula: ``precompile`` walks reservations through this
        same helper, so the precompiled grid always matches runtime."""
        fns = self.fns
        vmin = pow2_at_least(blocks_for(
            max(MIN_CHUNK_VIEW_ROWS, fns.block_size), fns.block_size
        ))
        return min(
            fns.max_blocks_per_seq, max(pow2_at_least(n_blocks), vmin)
        )

    def _chunk_view_blocks(self, state) -> int:
        return self._view_blocks(len(state.block_ids))

    def _cow(self, state, block_index: int) -> None:
        """Copy-on-write: the lane is about to write into a block other
        tables (or the prefix index) still need — give it a private
        bit-identical copy first.  The copy target was pre-allocated at
        admission when the trigger was known (fully-cached prompt);
        otherwise one block is drawn from the pool."""
        src = state.block_ids[block_index]
        if state.cow_block is not None:
            dst, state.cow_block = state.cow_block, None
        else:  # structurally unreachable today; guard stays honest
            dst = self.allocator.alloc(1)[0]
        if self._cow_prog is None:
            self._cow_prog = jax.jit(pool_copy_block)
        with jax.set_mesh(self.fns.mesh):
            self.pools = self._cow_prog(
                self.pools, jnp.int32(src), jnp.int32(dst)
            )
        state.block_ids[block_index] = dst
        self.allocator.free([src])  # drop this lane's share of the original
        self.stats["cow_copies"] += 1
        if self.obs is not None:
            self.obs.emit(
                "kv_cow_copy",
                request_id=state.request.id,
                src=src, dst=dst, block_index=block_index,
            )

    def _advance_prefill(self) -> None:
        """Run ONE chunk of the oldest still-prefilling lane — the
        scheduler-loop interleaving that bounds how long any prompt can
        keep the batched decode dispatch waiting."""
        lanes = [
            s for s in self.scheduler.active() if not s.prefill_done
        ]
        if not lanes:
            return
        self._prefill_chunk(min(lanes, key=lambda s: s.admitted_at))

    def _prefill_chunk(self, state) -> None:
        fns = self.fns
        req = state.request
        p = req.prompt_len
        off = state.prefill_pos
        remaining = p - off
        c = min(
            remaining,
            self.prefill_chunk if self.prefill_chunk else remaining,
        )
        cb = prompt_bucket(c, fns.block_size)
        nmax_rows = self._chunk_view_blocks(state) * fns.block_size
        # the bucket rounds the chunk UP, and a late start can push
        # the padded end past the gathered view (e.g. a 17-token
        # tail at off 40 buckets to 32 rows against a 64-row view:
        # 72 > 64).  dynamic_slice would then CLAMP the start and
        # silently read/write the wrong rows — shrink the chunk so
        # the padded span fits; the remainder runs as another chunk
        while off + cb > nmax_rows:
            cb //= 2
        assert cb >= fns.block_size, (off, cb, nmax_rows)
        c = min(c, cb)
        mode = "final" if off + c >= p else "mid"
        # write-path CoW guard: the span scatter targets only the
        # lane's private tail by construction (chunk starts are
        # block-aligned past the shared prefix), EXCEPT the fully-
        # cached recompute of the last shared block — any block that
        # is still shared or index-registered gets a private
        # bit-identical copy before being written (the scheduler
        # pre-allocated the copy target as state.cow_block)
        for bi in range(
            off // fns.block_size,
            min(-(-(off + cb) // fns.block_size), len(state.block_ids)),
        ):
            bid = state.block_ids[bi]
            if (
                self.allocator.refcount(bid) > 1
                or self.allocator.is_indexed(bid)
            ):
                self._cow(state, bi)
        final = mode != "mid"
        nmax = self._chunk_view_blocks(state)
        tokens = np.zeros((1, cb), np.int32)
        tokens[0, :c] = req.prompt[off:off + c]
        table = np.full((nmax,), fns.num_blocks, np.int32)
        n = min(nmax, len(state.block_ids))
        table[:n] = state.block_ids[:n]
        t0 = perf_counter()
        prog, _ = fns.chunk_for(cb, nmax, mode)
        before = _jit_compiles(prog)
        operands = (
            tokens, table, np.int32(off), np.int32(c - 1),
            self._prefill_rng(req),
        )
        out = self._dispatch(prog, *operands)
        if final:
            tok0, rng, self.pools = out
            tok0 = int(tok0)  # fences the first token: a REAL TTFT
        else:
            self.pools = out
            jax.block_until_ready(
                self.pools[0].kq
                if isinstance(self.pools[0], QuantKV) else self.pools[0][0]
            )
        compiled = _jit_compiles(prog) != before
        if compiled:
            self.stats["prefill_compiles"] += 1
            state.cold = True
            self._emit_hbm_plan(
                f"serve_chunk_c{cb}_n{nmax}_{mode}", prog,
                self._args(*operands),
            )
        self.stats["prefill_tokens"] += c
        self.stats["prefill_chunks"] += 1
        chunk_idx = state.prefill_chunks
        state.prefill_chunks += 1
        state.prefill_pos = off + c
        self._emit_trace_span(
            "prefill", t0, perf_counter(),
            trace=req.id, span=f"{req.id}/p{chunk_idx}",
            parent=f"{req.id}/req", traced=req.traced,
            request_id=req.id, lane=state.lane,
            bucket=cb, chunk=chunk_idx, offset=off, compiled=compiled,
            mode=mode,
            **tenant_tags(req),
        )
        if final:
            self._finish_prefill(state, tok0, rng, cold=compiled)

    def _decode_batch(self) -> None:
        fns = self.fns
        # a lane can be done straight out of admission (max_new=1: the
        # prefill's sampled token IS the whole output, finished_at set
        # at prefill completion) — it waits for the next retire pass and
        # must not enter the chunk-length min below (remaining would be
        # 0).  Lanes still mid-chunked-prefill have no pending token yet
        # and sit the dispatch out too.
        active = [
            s for s in self.scheduler.active()
            if s.prefill_done and not s.done
        ]
        if not active:
            return
        # chunk length: fuse up to max_steps_per_dispatch single-token
        # steps into one program, but never past the soonest lane
        # completion — retire/admit stay exact, and no lane ever decodes
        # beyond its max_new.  Power-of-two floor bounds the program grid.
        remaining = min(
            s.request.max_new - len(s.outputs) for s in active
        )
        k = pow2_at_most(min(remaining, self.max_steps_per_dispatch))
        # table width: the widest active reservation, rounded up — short
        # requests must not pay gather+attention over the whole pool
        nmax = min(
            pow2_at_least(max(len(s.block_ids) for s in active)),
            fns.max_blocks_per_seq,
        )
        invalid = fns.num_blocks
        tables = np.full((fns.max_batch, nmax), invalid, np.int32)
        lengths = np.zeros((fns.max_batch,), np.int32)
        pending = np.zeros((fns.max_batch,), np.int32)
        for s in active:
            n = min(nmax, len(s.block_ids))
            tables[s.lane, :n] = s.block_ids[:n]
            lengths[s.lane] = s.length
            pending[s.lane] = s.pending_tok
        seq = self.stats["decode_dispatches"]  # this dispatch's number
        t0 = perf_counter()
        prog, _ = fns.decode_for(k, nmax)
        before = _jit_compiles(prog)
        toks, self._rngs, self.pools = self._dispatch(
            prog, tables, lengths, pending, self._rngs
        )
        # executable-count detection (see _full_prefill): the second
        # call of a program recompiles for the committed-pools signature
        # — a first-build flag alone would warm-mark that dispatch
        if _jit_compiles(prog) != before:
            self.stats["decode_compiles"] += 1
            for s in active:
                s.cold = True
            self._emit_hbm_plan(
                f"serve_decode_k{k}_n{nmax}", prog,
                self._args(tables, lengths, pending, self._rngs),
            )
        self.stats["decode_steps"] += k
        self.stats["decode_dispatches"] += 1
        toks = np.asarray(toks)  # (K, B): ONE fence per chunk
        now = perf_counter()
        for s in active:
            s.length += k
            lane_toks = toks[:, s.lane]
            s.pending_tok = int(lane_toks[-1])
            s.outputs.extend(int(t) for t in lane_toks)
            s.dispatches.append(seq)
            # one causal span PER RIDING REQUEST, not per dispatch: the
            # trace of request X must show every batched dispatch X's
            # tokens came out of, with the co-riders in args
            self._emit_trace_span(
                "decode", t0, now,
                trace=s.request.id, span=f"{s.request.id}/d{seq}",
                parent=f"{s.request.id}/req", traced=s.request.traced,
                request_id=s.request.id, lane=s.lane, dispatch=seq,
                steps=k, riders=len(active),
                **tenant_tags(s.request),
            )
            if s.done:
                s.finished_at = now

    def drain(self, reason: str = "preempt", park: bool = False) -> dict:
        """Close admission and shed everything queued (tenant-tagged
        ``serve_shed`` events, reason ``"drained"``); in-flight lanes
        keep decoding to completion through subsequent ``step()`` calls
        — the drain is a taper, not a cliff.  ``park=True`` is the hard
        stop for a deadline the taper cannot meet: every unfinished
        lane is retired NOW (blocks recycled, no torn refcounts), its
        partial outputs recorded under outcome ``parked:<reason>`` AND
        its full resume state kept in ``self.parked`` — after the
        restart boundary, :meth:`resume_parked` re-admits each one and
        completes its stream token-identically.  Idempotent; emits one
        ``serve_drain`` event with the shed/parked counts."""
        if self.draining and not park:
            return {"shed": 0, "parked": 0}
        first = not self.draining
        self.draining = True
        self.drain_reason = self.drain_reason or reason
        shed = 0
        while self.admission.queue:
            self.admission.shed_request(self.admission.pop(), "drained")
            self.stats["shed"] += 1
            shed += 1
        parked = 0
        if park:
            # finished lanes retire through the normal path first (full
            # decode record + completed count); only genuinely
            # unfinished lanes park
            self._retire_finished()
            for state in self.scheduler.park_all():
                if state.request.id in self.results:
                    continue  # finished lane: retired with its result
                self.results[state.request.id] = np.asarray(
                    state.outputs, np.int32
                )
                self.outcomes[state.request.id] = f"parked:{reason}"
                # resume cursor: the partial outputs plus the lane's rng
                # CARRY (the state after the last sampled token) — what
                # resume_parked() re-prefills and re-seeds from so the
                # completed stream is token-identical to an
                # uninterrupted decode.  A lane parked mid-chunked-
                # prefill has produced nothing — it resumes as a plain
                # resubmit (rng None -> seed from rng_seed as usual).
                self.parked[state.request.id] = {
                    "request": state.request,
                    "outputs": list(state.outputs),
                    "rng": (
                        np.asarray(
                            jax.device_get(self._rngs[state.lane]),
                            np.uint32,
                        )
                        if state.prefill_done and state.outputs else None
                    ),
                }
                parked += 1
        if self.obs is not None and (first or parked):
            self.obs.emit(
                "serve_drain",
                reason=reason,
                shed=shed,
                parked=parked,
                active_lanes=len(self.scheduler.active()),
            )
        return {"shed": shed, "parked": parked}

    def resume_parked(self) -> dict:
        """Re-open admission and resubmit every request parked by
        ``drain(park=True)`` — the serving half of an elastic grow
        epoch.  Each parked request re-enters through NORMAL admission
        (same id, same tenant tags) with its prompt extended by the
        tokens it already generated: prefill recomputes their KV rows
        (the park recycled its blocks), the recorded rng carry seeds the
        continuation, and ``_retire_finished`` prepends the prefix back
        — so the completed stream is token-identical to a decode that
        was never interrupted (greedy trivially; sampled because the
        carry replays the exact split sequence).  The pool footprint is
        unchanged: (p + j) + (m - j) - 1 = p + m - 1 cache rows.
        Returns ``{"resumed", "rejected"}``; a request the (possibly
        smaller) new world cannot ever fit is shed through the normal
        admission path, never silently dropped."""
        self.draining = False
        self.drain_reason = None
        parked, self.parked = self.parked, {}
        resumed = rejected = 0
        for rid, rec in parked.items():
            req = rec["request"]
            outputs = rec["outputs"]
            if len(outputs) >= req.max_new:
                # defensive: a record that is actually complete
                self.results[rid] = np.asarray(outputs, np.int32)
                self.outcomes[rid] = "ok"
                continue
            prompt = np.asarray(req.prompt, np.int32).reshape(-1)
            if outputs:
                prompt = np.concatenate(
                    [prompt, np.asarray(outputs, np.int32)]
                )
            new = Request(
                id=rid,
                prompt=prompt,
                max_new=req.max_new - len(outputs),
                submitted_at=req.submitted_at,
                rng_seed=req.rng_seed,
                traced=req.traced,
                tenant=req.tenant,
                priority_class=req.priority_class,
                resume_prefix=list(outputs),
                resume_rng=rec["rng"],
            )
            # the parked partials were surfaced under parked:<reason>;
            # the resumed completion replaces them
            self.results.pop(rid, None)
            self.outcomes.pop(rid, None)
            outcome = self.admission.offer(
                new, fits_ever=self.scheduler.fits_ever(new)
            )
            if outcome == "rejected":
                self.stats["shed"] += 1
                rejected += 1
            else:
                resumed += 1
            if self.obs is not None:
                self.obs.emit(
                    "serve_resume",
                    request_id=rid,
                    resumed_tokens=len(outputs),
                    remaining=new.max_new,
                    outcome=outcome,
                    **tenant_tags(new),
                )
        return {"resumed": resumed, "rejected": rejected}

    def step(self) -> bool:
        """One scheduler iteration; False when fully drained.  Order:
        retire -> admit -> ONE prefill chunk -> one batched decode
        dispatch — chunked prefills and decode interleave, so a long
        prompt stalls the decode batch for at most one bounded chunk
        per iteration instead of its whole prefill.  When the
        preemption guard trips (or ``drain()`` was called), admission
        stops and the in-flight lanes finish instead of the engine
        dying mid-dispatch."""
        if (
            not self.draining
            and self.guard is not None
            and getattr(self.guard, "requested", False)
        ):
            self.drain("preempt")
        self._retire_finished()
        while not self.draining and self.admission.queue:
            head = self.admission.peek()
            # ONE chain-hash lookup per head per iteration, threaded
            # through fits/can_admit/admit (hashing a parked 32k prompt
            # three times per scheduler tick would tax the loop that
            # chunked prefill exists to keep responsive)
            shared = self.scheduler.cached_prefix(head)
            if not self.scheduler.fits_ever(head, len(shared)):
                # defensive re-check: under the CURRENT accounting
                # fits_ever is invariant to cache eviction (sharing
                # never changes a request's total residency), so a head
                # that passed at offer time cannot fail here.  The
                # guard stays because a future admission-policy change
                # that breaks the invariant would otherwise park the
                # head forever and livelock the drain loop behind it.
                self.admission.shed_request(self.admission.pop(), "too_large")
                self.stats["shed"] += 1
                continue
            if not self.scheduler.can_admit(head, shared):
                break
            self._admit_one(self.admission.pop(), shared)
        self._advance_prefill()
        if self.scheduler.active():
            self._decode_batch()
        if (
            self.defrag_threshold is not None
            and self.allocator.fragmentation() > self.defrag_threshold
        ):
            self.defrag()
        return self.busy

    def run(self) -> dict[str, np.ndarray]:
        """Drive to drain; returns completed outputs by request id
        (shed requests appear in ``outcomes`` only)."""
        while self.step():
            pass
        self._retire_finished()
        return self.results

    def pop_result(self, request_id: str) -> np.ndarray:
        """Hand over and FORGET one completed request's tokens.  The
        drain-once bench reads ``results`` wholesale, but a continuous
        server must evict as it responds — ``results``/``outcomes``
        otherwise grow by one entry per request served, forever."""
        self.outcomes.pop(request_id, None)
        return self.results.pop(request_id)

    def precompile(self, max_prompt_len: int, max_new: int) -> dict:
        """Compile every program a client mix bounded by
        ``(max_prompt_len, max_new)`` can reach — all smaller prefill
        buckets plus the full (chunk length, table width) decode grid —
        so steady-state requests never pay an XLA compile (the serving
        twin of a bench warmup epoch; the grid is log x log, so this is
        a handful of programs, not one per shape).

        Dummy inputs drive each program TWICE, threading the output
        pools (and rng states) back in: jit keys on operand commitment,
        so the first call compiles the fresh-input signature and the
        second the steady-state one where pools/rngs are prior program
        outputs — the signature every loop iteration after the first
        actually hits.  The dummies go through the same ``_dispatch`` as
        live requests (numpy operands, a PRNGKey made outside the mesh
        context), so their signature IS the live one.  Every dummy block
        id is out of range, so pool
        writes drop and the pool CONTENT is untouched (the committed
        arrays are kept, matching the steady-state signature).
        Returns ``{"prefill": n, "decode": m, "chunk": c}``
        newly-compiled counts (also in ``stats['precompiled_*']``)."""
        fns = self.fns
        compiled = {"prefill": 0, "decode": 0, "chunk": 0}
        top_bucket = prompt_bucket(max(1, max_prompt_len), fns.block_size)
        buckets = []
        b = fns.block_size
        while b < top_bucket:
            buckets.append(b)
            b *= 2
        buckets.append(top_bucket)
        if self.prefill_chunk is not None:
            # prompts longer than the chunk bound run as chunk programs,
            # never through a whole-prompt prefill bucket — don't pay
            # those compiles
            full_cap = prompt_bucket(self.prefill_chunk, fns.block_size)
            buckets = [b for b in buckets if b <= full_cap]
        # decode grid FIRST: the decode jit pins the pending-token
        # sharding, so its outputs are committed regardless of input
        # state — after one feedback pass ``self.pools``/rngs are
        # committed, which is the signature every later program (incl.
        # the prefill buckets below: prefill has no explicit shardings,
        # so an all-uncommitted pass would never leave that state) sees
        # in the real loop
        max_blocks = min(
            blocks_for(
                max(1, max_prompt_len) + max(1, max_new) - 1,
                fns.block_size,
            ),
            fns.max_blocks_per_seq,
        )
        nmaxes = sorted({
            min(pow2_at_least(n), fns.max_blocks_per_seq)
            for n in range(1, max_blocks + 1)
        })
        ks = [
            1 << i
            for i in range(pow2_at_most(self.max_steps_per_dispatch)
                           .bit_length())
        ]
        zeros = np.zeros((fns.max_batch,), np.int32)
        # like every live admission's key: made outside the mesh context
        key0 = jax.random.PRNGKey(0)
        # ONE rng state threaded across the whole grid, starting from
        # the live one (never written back): committed after the first
        # program's feedback pass, so every later program's first call
        # already carries the steady-state signature
        rngs = self._rngs
        for nmax in nmaxes:
            t = np.full((fns.max_batch, nmax), fns.num_blocks, np.int32)
            for k in ks:
                prog, built = fns.decode_for(k, nmax)
                if not built:
                    continue
                for _ in range(2):
                    out = self._dispatch(prog, t, zeros, zeros, rngs)
                    jax.block_until_ready(out[0])
                    rngs, self.pools = out[1], out[2]
                compiled["decode"] += 1
                self._emit_hbm_plan(
                    f"serve_decode_k{k}_n{nmax}", prog,
                    self._args(t, zeros, zeros, rngs),
                )
        for bucket in buckets:
            if bucket in self._compiled_buckets:
                continue
            prog = fns.prefill_for(bucket)
            operands = (
                np.zeros((1, bucket), np.int32),
                np.full(
                    (bucket // fns.block_size,), fns.num_blocks, np.int32
                ),
                np.int32(1),
                key0,
            )
            for _ in range(2):
                out = self._dispatch(prog, *operands)
                jax.block_until_ready(out[0])
                self.pools = out[2]
            # mimic the admit path's eager ops (int() fence, per-lane
            # rng scatter) so their one-time op compiles happen here,
            # not inside the first timed admissions; lane 0's rng is
            # overwritten at every real admission, so the dummy is inert
            int(out[0])
            self._rngs = self._rngs.at[0].set(out[1])
            self._compiled_buckets.add(bucket)
            compiled["prefill"] += 1
            self._emit_hbm_plan(
                f"serve_prefill_b{bucket}", prog, self._args(*operands)
            )
        # chunk-prefill programs: reachable when prompts can continue a
        # cached prefix (prefix cache on) or exceed the chunk bound.
        # View widths ride the same reservation-derived grid as decode,
        # floored at the MIN_CHUNK_VIEW_ROWS clamp.
        modes = []
        if self.prefill_chunk is not None or self.prefix is not None:
            # "mid" is reachable WITHOUT a chunk bound too: the view
            # clamp in _prefill_chunk can shrink a prefix-hit tail
            # below its remainder, leaving a mid chunk to finish it
            modes = ["mid", "final"]
        if modes:
            vmaxes = sorted({
                self._view_blocks(n) for n in range(1, max_blocks + 1)
            })
            cap = (
                min(self.prefill_chunk, top_bucket)
                if self.prefill_chunk else top_bucket
            )
            cbs = [b for b in buckets if b <= cap] or [fns.block_size]
            for nmax in vmaxes:
                t = np.full((nmax,), fns.num_blocks, np.int32)
                for mode in modes:
                    for cb in cbs:
                        if cb > nmax * fns.block_size:
                            # a chunk never outgrows its own view: the
                            # runtime width covers the lane's WHOLE
                            # reservation (>= off + cb rows)
                            continue
                        prog, built = fns.chunk_for(cb, nmax, mode)
                        if not built:
                            continue
                        # the same fresh key on both passes, like the
                        # real chunk dispatches (threading the rng
                        # output back in would precompile a
                        # committed-rng signature the runtime never
                        # presents)
                        operands = (
                            np.zeros((1, cb), np.int32), t,
                            np.int32(0), np.int32(0), key0,
                        )
                        for _ in range(2):
                            out = self._dispatch(prog, *operands)
                            if mode == "mid":
                                self.pools = out
                                jax.block_until_ready(
                                    self.pools[0].kq
                                    if isinstance(self.pools[0], QuantKV)
                                    else self.pools[0][0]
                                )
                            else:
                                jax.block_until_ready(out[0])
                                self.pools = out[2]
                        compiled["chunk"] += 1
                        self._emit_hbm_plan(
                            f"serve_chunk_c{cb}_n{nmax}_{mode}", prog,
                            self._args(*operands),
                        )
            if self.prefix is not None and self._cow_prog is None:
                # the CoW copy program: src == dst is a content no-op
                self._cow_prog = jax.jit(pool_copy_block)
                last = jnp.int32(fns.num_blocks - 1)
                for _ in range(2):
                    with jax.set_mesh(fns.mesh):
                        self.pools = self._cow_prog(self.pools, last, last)
        self.stats["precompiled_prefill"] = (
            self.stats.get("precompiled_prefill", 0) + compiled["prefill"]
        )
        self.stats["precompiled_decode"] = (
            self.stats.get("precompiled_decode", 0) + compiled["decode"]
        )
        self.stats["precompiled_chunk"] = (
            self.stats.get("precompiled_chunk", 0) + compiled["chunk"]
        )
        return compiled

    def warmup(self, prompt_len: int, max_new: int = 2) -> None:
        """Compile the decode program and the bucket for ``prompt_len``
        ahead of timing (the serving twin of a bench warmup epoch).
        Drives everything TWICE: each program compiles once for the
        fresh-pools signature and once for the committed-pools one (see
        ``precompile``) — a single pass would leave the second compile
        inside the first timed request."""
        prev_trace, self.trace_requests = self.trace_requests, False
        # the synthetic prompt must not enter the prefix index (a real
        # request could hit its blocks) nor hit it (the second warmup
        # pass would take the cached path instead of re-driving the full
        # prefill program it exists to warm)
        prev_prefix = self.prefix
        self.prefix = self.scheduler.prefix_index = None
        try:
            self._warmup_requests(prompt_len, max_new)
        finally:
            # the synthetic request must not become a trace (it would
            # win --slowest-request on its compile time every smoke)
            self.trace_requests = prev_trace
            self.prefix = self.scheduler.prefix_index = prev_prefix

    def _warmup_requests(self, prompt_len: int, max_new: int) -> None:
        for _ in range(2):
            outcome = self.submit(
                np.zeros((prompt_len,), np.int32), max_new,
                request_id="_warmup",
            )
            if outcome != "queued":
                return
            self.run()
            self.results.pop("_warmup", None)
            self.outcomes.pop("_warmup", None)
            self.request_log = deque(
                (r for r in self.request_log
                 if r.get("request_id") != "_warmup"),
                maxlen=self.request_log.maxlen,
            )
            self.stats["submitted"] -= 1
            self.stats["completed"] -= 1

    def defrag(self) -> bool:
        """Compact live blocks to the lowest pool ids (device copy +
        table rewrite); returns whether anything moved."""
        plan = self.allocator.compaction_plan()
        if not plan:
            return False
        self.pools = apply_block_permutation(
            self.pools, plan, self.fns.num_blocks
        )
        self.scheduler.remap_blocks(plan)
        if self.prefix is not None:
            # cached (evictable) blocks move too — the index follows
            self.prefix.remap(plan)
        self.allocator.commit_plan(plan)
        self._emit_pool_stats(defrag=True)
        return True
