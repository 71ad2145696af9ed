"""Paged/block KV cache: a fixed pool of block-granular KV slots.

One-shot decode (``infer/decode.py``) allocates a contiguous
``(B, max_len, Hkv*Dh)`` cache per generator — the right shape for a
single fused program, the wrong shape for serving: a continuous batch
admits and retires requests at different lengths every iteration, so a
contiguous per-request allocation either reserves worst-case capacity
for everyone (the memory waste the vLLM paper measured at 60-80%) or
copies caches around on every admit.  The paged layout breaks the cache
into fixed-size blocks:

* device side, per layer: ``k``/``v`` pools of shape
  ``(num_blocks, block_size, Hkv*Dh)`` — the SAME fused feature-minor
  storage as ``infer/decode.init_kv_cache`` (``ops/quant.kv_fuse``:
  in-place single-row writes), just chopped along the sequence dim into
  block rows.  The int8 path reuses ``ops.quant.QuantKV`` exactly:
  int8 pools plus ``(num_blocks, Hkv, block_size)`` f32 scale pools.
* host side: ``BlockAllocator`` — a free list over block ids with
  allocate/free/defrag and the occupancy stats the admission policy
  watches (``serve/admission.py``); each in-flight request holds a
  **block table** (list of block ids), and the decode step gathers each
  lane's table into a contiguous per-lane view (``pool_gather``) that
  feeds the unmodified cached-attention cores (``ops.quant.kv_attend``
  — einsum or the Pallas one-pass kernel with a per-lane bias row).

Prefix caching (round 17): blocks carry **refcounts** so several
requests' block tables can point at the same physical block read-only —
thousands of requests sharing a system prompt share its K/V blocks
instead of each recomputing and re-storing them.  The ``PrefixIndex``
keys blocks by a chain hash of the token-id prefix at block granularity;
a block whose last owner retires keeps its content and parks in an LRU
**evictable** set (still indexed, reclaimed only under allocation
pressure), so the cache survives between bursts at zero steady-state
cost.  Decode appends only ever write a request's private tail blocks,
so sharing is copy-free in steady state; the one write a shared block
can see (recomputing the final prompt token of a fully-cached
block-aligned prompt) goes through ``pool_copy_block`` copy-on-write.

Sharding: the pool's block dim is the sequence dim chopped up, so it
carries the ``act_seq`` logical axis (context-parallel serving shards
the pool over ``seq``); the fused feature dim keeps ``act_heads``
(tensor-parallel decode).  Validated by the ``serve_decode`` contract
probe (``analysis/contracts.py``).
"""

from __future__ import annotations

import bisect
import dataclasses
import hashlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ddl_tpu.infer.kv_cache import (
    CACHE_SPEC,
    constrain_kv,
    decode_attention_path,
    zeros_kv,
)
from ddl_tpu.ops.quant import QuantKV, kv_attend, quantize_q8

__all__ = [
    "BlockAllocator",
    "PagedKV",
    "POOL_SPEC",
    "PoolExhausted",
    "PrefixIndex",
    "blocks_for",
    "cache_write_token",
    "init_kv_pool",
    "pool_copy_block",
    "pool_gather",
    "pool_write_prefill",
    "pool_write_token",
    "apply_block_permutation",
]


def blocks_for(tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``tokens`` cache rows (ceil division)."""
    if tokens <= 0:
        raise ValueError(f"tokens must be > 0, got {tokens}")
    return -(-tokens // block_size)


class PoolExhausted(RuntimeError):
    """Raised by ``BlockAllocator.alloc`` when the pool cannot satisfy a
    request — the scheduler checks ``can_alloc`` first, so reaching this
    from the engine is a bookkeeping bug, not an overload condition."""


class BlockAllocator:
    """Host-side refcounted free list over the pool's block ids.

    Lowest-id-first allocation keeps live blocks packed toward the front
    of the pool (gathers touch a compact prefix; ``defrag`` restores the
    property when interleaved retire/admit churn breaks it).

    Every live block carries a **refcount**: ``alloc`` hands out private
    blocks at refcount 1, ``share`` lets another request's block table
    point at an existing block (+1), and ``free`` decrements — a block
    returns to circulation only when its last owner retires.  A block
    the ``PrefixIndex`` has registered (``mark_indexed``) does not go
    back to the free list at refcount 0: it parks in the LRU
    **evictable** set with its content intact, ready to be ``share``d
    by the next request with the same prefix, and is reclaimed (oldest
    first, ``on_evict`` notified so the index forgets it) only when
    ``alloc`` runs out of free blocks.

    Invariants (pinned by tests/test_serve.py + test_serve_prefix.py):
    a block is never handed out twice, never freed below refcount 0
    (double-free raises), never evicted while referenced, and
    ``free + refcounted + evictable == num_blocks`` always.
    """

    def __init__(self, num_blocks: int, block_size: int) -> None:
        if num_blocks < 1 or block_size < 1:
            raise ValueError(
                f"need num_blocks >= 1 and block_size >= 1, got "
                f"{num_blocks}, {block_size}"
            )
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._free = list(range(num_blocks))  # kept ascending
        self._refs: dict[int, int] = {}  # live block -> refcount >= 1
        self._evictable: dict[int, None] = {}  # ref==0 indexed blocks, LRU
        self._indexed: set[int] = set()  # blocks the PrefixIndex holds
        self.on_evict = None  # callable(block_id): index forget hook
        self.high_water = 0  # max blocks ever simultaneously in use
        self.evictions = 0

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Blocks with a live owner (refcount >= 1)."""
        return len(self._refs)

    @property
    def cached_blocks(self) -> int:
        """Indexed refcount-0 blocks holding reusable prefix content."""
        return len(self._evictable)

    def refcount(self, block_id: int) -> int:
        return self._refs.get(block_id, 0)

    def is_indexed(self, block_id: int) -> bool:
        """Whether the PrefixIndex holds this block (its content must
        not be overwritten by a live owner — CoW first)."""
        return block_id in self._indexed

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free) + len(self._evictable)

    def alloc(self, n: int) -> list[int]:
        if n < 1:
            raise ValueError(f"alloc needs n >= 1, got {n}")
        if not self.can_alloc(n):
            raise PoolExhausted(
                f"need {n} blocks, {len(self._free)} free + "
                f"{len(self._evictable)} evictable of {self.num_blocks}"
            )
        while len(self._free) < n:
            self._evict_one()
        ids, self._free = self._free[:n], self._free[n:]
        for i in ids:
            self._refs[i] = 1
        self.high_water = max(self.high_water, len(self._refs))
        return ids

    def _evict_one(self) -> None:
        """Reclaim the least-recently-released evictable block: the
        prefix index forgets it (``on_evict``) and it joins the free
        list — the LRU-on-refcount-0 watermark eviction.  Insort, not a
        re-sort: ``alloc`` evicts in a loop, and a long prompt admitted
        into a pool full of cached blocks (the prefix cache's steady
        state) would otherwise re-sort the free list once per block."""
        bid = next(iter(self._evictable))
        del self._evictable[bid]
        self._indexed.discard(bid)
        self.evictions += 1
        if self.on_evict is not None:
            self.on_evict(bid)
        bisect.insort(self._free, bid)

    def share(self, ids) -> None:
        """Add one owner to each block: a request's block table now
        points at it read-only.  Reactivates evictable (cached) blocks;
        sharing a free block is a bookkeeping bug and raises."""
        ids = list(ids)
        bad = [
            i for i in ids if i not in self._refs and i not in self._evictable
        ]
        if bad:
            raise ValueError(
                f"sharing blocks with no live or cached content: "
                f"{sorted(bad)}"
            )
        for i in ids:
            if i in self._evictable:
                del self._evictable[i]
                self._refs[i] = 1
            else:
                self._refs[i] += 1
        self.high_water = max(self.high_water, len(self._refs))

    def free(self, ids) -> None:
        """Drop one owner per block.  At refcount 0 an indexed block
        parks in the evictable set (content kept for the next prefix
        hit); an unindexed one returns to the free list."""
        ids = list(ids)
        bad = [i for i in ids if i not in self._refs]
        if bad:
            raise ValueError(
                f"freeing blocks not currently allocated: {sorted(bad)}"
            )
        released = []
        for i in ids:
            self._refs[i] -= 1
            if self._refs[i] == 0:
                del self._refs[i]
                if i in self._indexed:
                    self._evictable[i] = None  # LRU: append on release
                else:
                    released.append(i)
        if released:
            self._free = sorted(self._free + released)

    def mark_indexed(self, block_id: int) -> None:
        """The PrefixIndex registered this block: at refcount 0 it will
        be cached (evictable), not freed."""
        if block_id not in self._refs and block_id not in self._evictable:
            raise ValueError(f"indexing a free block: {block_id}")
        self._indexed.add(block_id)

    def drop_indexed(self, block_id: int) -> None:
        """Un-index a block (the public inverse of ``mark_indexed``,
        for an external invalidation path — no in-tree caller today;
        eviction uses ``_evict_one``): an evictable block returns to
        the free list immediately."""
        self._indexed.discard(block_id)
        if block_id in self._evictable:
            del self._evictable[block_id]
            bisect.insort(self._free, block_id)

    def _live(self) -> set[int]:
        return set(self._refs) | set(self._evictable)

    def fragmentation(self) -> float:
        """Fraction of the live span that is holes: 1 - live/(max+1).
        0.0 when live (refcounted or cached) blocks are packed at the
        front — the quantity ``defrag`` drives back to zero."""
        live = self._live()
        if not live:
            return 0.0
        span = max(live) + 1
        return 1.0 - len(live) / span

    def compaction_plan(self) -> dict[int, int] | None:
        """old-id -> new-id mapping that packs live AND cached blocks to
        the lowest ids (preserving relative order), or None when already
        packed.  The caller must apply it to the device pools, every
        request's block table (``apply_block_permutation``) and the
        ``PrefixIndex`` (``remap``), then ``commit_plan``."""
        live = sorted(self._live())
        plan = {old: new for new, old in enumerate(live) if old != new}
        return plan or None

    def commit_plan(self, plan: dict[int, int]) -> None:
        """Adopt a compaction plan: live blocks occupy [0, live)."""
        self._refs = {plan.get(i, i): r for i, r in self._refs.items()}
        self._evictable = {
            plan.get(i, i): None for i in self._evictable
        }  # dict comprehension preserves LRU order
        self._indexed = {plan.get(i, i) for i in self._indexed}
        self._free = sorted(
            set(range(self.num_blocks)) - set(self._refs)
            - set(self._evictable)
        )

    def stats(self) -> dict:
        return {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "free": self.free_blocks,
            "used": self.used_blocks,
            "cached": self.cached_blocks,
            "shared": sum(1 for r in self._refs.values() if r > 1),
            "evictions": self.evictions,
            "high_water": self.high_water,
            "fragmentation": round(self.fragmentation(), 4),
        }


class PrefixIndex:
    """Content-keyed index over pool blocks holding prompt prefixes.

    Key: a chain hash over the token ids at block granularity —
    ``key_i = H(key_{i-1} || tokens[i*bs:(i+1)*bs])`` — so a block's key
    commits to the WHOLE prefix through it, not just its own tokens
    (two prompts sharing block 3's tokens but not block 2's can never
    collide).  ``lookup`` walks the chain and returns the longest run of
    cached blocks; ``insert`` registers a finished prefill's full prompt
    blocks.  Pure host-side maps; block lifetime (refcounts, LRU
    eviction) lives in ``BlockAllocator`` — the allocator calls
    ``forget_block`` when it evicts, the engine calls ``remap`` after a
    defrag.
    """

    def __init__(self, block_size: int) -> None:
        self.block_size = int(block_size)
        self._by_key: dict[str, int] = {}
        self._by_block: dict[int, str] = {}

    def __len__(self) -> int:
        return len(self._by_key)

    def chain_keys(self, tokens) -> list[str]:
        """One key per FULL block of ``tokens`` (len // block_size)."""
        toks = np.asarray(tokens, np.int32).reshape(-1)
        keys = []
        h = b""
        for i in range(len(toks) // self.block_size):
            blk = toks[i * self.block_size:(i + 1) * self.block_size]
            h = hashlib.sha1(h + blk.tobytes()).digest()
            keys.append(h.hex())
        return keys

    def lookup(self, tokens, keys: list[str] | None = None) -> list[int]:
        """Block ids of the longest cached block-aligned prefix of
        ``tokens`` (full blocks only; possibly empty).  ``keys`` lets a
        caller reuse one ``chain_keys`` pass — the hash is a pure
        function of the immutable prompt, only this dict walk needs to
        be fresh (a queue head is re-looked-up every scheduler tick)."""
        ids = []
        for key in keys if keys is not None else self.chain_keys(tokens):
            bid = self._by_key.get(key)
            if bid is None:
                break
            ids.append(bid)
        return ids

    def insert(
        self, tokens, block_ids, allocator: BlockAllocator,
        keys: list[str] | None = None,
    ) -> int:
        """Register ``tokens``'s full-block prefix as cached content in
        ``block_ids`` (the request's block table).  Blocks already
        indexed under the same key are skipped (first writer wins — both
        copies hold identical K/V, only one is worth keeping); returns
        how many blocks were newly registered."""
        new = 0
        if keys is None:
            keys = self.chain_keys(tokens)
        for i, key in enumerate(keys):
            if i >= len(block_ids):
                break
            if key in self._by_key:
                continue
            bid = int(block_ids[i])
            if bid in self._by_block:
                # the block already backs a different chain position
                # (cannot happen for distinct live tables, but a stale
                # insert after eviction could) — keep the existing entry
                continue
            self._by_key[key] = bid
            self._by_block[bid] = key
            allocator.mark_indexed(bid)
            new += 1
        return new

    def forget_block(self, block_id: int) -> None:
        """Allocator eviction hook: drop the block's index entry."""
        key = self._by_block.pop(block_id, None)
        if key is not None:
            self._by_key.pop(key, None)

    def remap(self, plan: dict[int, int]) -> None:
        """Rewrite block ids per a defrag compaction plan."""
        self._by_block = {
            plan.get(b, b): k for b, k in self._by_block.items()
        }
        self._by_key = {k: b for b, k in self._by_block.items()}


def init_kv_pool(
    cfg, num_blocks: int, block_size: int, dtype=None, quant: bool = False,
) -> tuple:
    """Per-layer zeroed block pools — ``init_kv_cache``'s layouts with the
    sequence dim chopped into ``num_blocks`` rows of ``block_size``.

    Plain: ``(k, v)`` of shape (num_blocks, block_size, Hkv*Dh).
    ``quant=True``: ``QuantKV`` leaves — int8 pools + (num_blocks, Hkv,
    block_size) f32 scales, the same per-(token, head) granularity as
    the contiguous int8 cache, so ``ops.quant.kv_attend`` reads a
    gathered pool without knowing it was paged."""
    pool = zeros_kv(cfg, num_blocks, block_size, dtype, quant)
    return tuple(pool for _ in range(cfg.n_layers))


def pool_write_prefill(pool_layer, cache_layer, block_ids):
    """Scatter one request's contiguous prefill cache into its blocks.

    ``cache_layer`` is a (1, Pb, fused) single-request cache (bf16 tuple
    or QuantKV) fresh out of ``infer.decode.LMDecode`` prefill;
    ``block_ids`` (Pb / block_size,) int32 — entries >= num_blocks are
    dropped (bucket padding beyond the request's reservation).  Rows
    past the true prompt length carry pad-token K/V; they are always
    overwritten by ``pool_write_token`` before the length mask ever
    exposes them."""
    if isinstance(pool_layer, QuantKV):
        bs = pool_layer.kq.shape[1]
        hkv = pool_layer.ks.shape[1]
        n = block_ids.shape[0]

        def rows(x):  # (1, Pb, fused) -> (n, bs, fused)
            return x[0].reshape(n, bs, x.shape[-1])

        def scales(s):  # (1, Hkv, Pb) -> (n, Hkv, bs)
            return s[0].reshape(hkv, n, bs).transpose(1, 0, 2)

        return QuantKV(
            pool_layer.kq.at[block_ids].set(
                rows(cache_layer.kq), mode="drop"
            ),
            pool_layer.ks.at[block_ids].set(
                scales(cache_layer.ks), mode="drop"
            ),
            pool_layer.vq.at[block_ids].set(
                rows(cache_layer.vq), mode="drop"
            ),
            pool_layer.vs.at[block_ids].set(
                scales(cache_layer.vs), mode="drop"
            ),
        )
    pk, pv = pool_layer
    ck, cv = cache_layer
    bs = pk.shape[1]
    n = block_ids.shape[0]
    rows = lambda x: x[0].reshape(n, bs, x.shape[-1])
    return (
        pk.at[block_ids].set(rows(ck).astype(pk.dtype), mode="drop"),
        pv.at[block_ids].set(rows(cv).astype(pv.dtype), mode="drop"),
    )


def pool_write_token(pool_layer, k, v, blk, slot):
    """Write one new K/V row per lane into the pool.

    ``k``/``v``: (B, 1, Hkv, Dh) fresh projections; ``blk``/``slot``:
    (B,) int32 — each lane's target block and in-block row.  Lanes with
    ``blk >= num_blocks`` (idle lanes) are dropped.  QuantKV pools
    quantize on the way in, exactly like ``ops.quant.kv_write``."""
    b = k.shape[0]
    kf = k.reshape(b, -1)  # fused (B, Hkv*Dh)
    vf = v.reshape(b, -1)
    if isinstance(pool_layer, QuantKV):
        kq, ks = quantize_q8(k)
        vq, vs = quantize_q8(v)
        kqf = kq.reshape(b, -1)
        vqf = vq.reshape(b, -1)
        kss = ks[:, 0, :, 0].astype(pool_layer.ks.dtype)  # (B, Hkv)
        vss = vs[:, 0, :, 0].astype(pool_layer.vs.dtype)
        return QuantKV(
            pool_layer.kq.at[blk, slot].set(kqf, mode="drop"),
            pool_layer.ks.at[blk, :, slot].set(kss, mode="drop"),
            pool_layer.vq.at[blk, slot].set(vqf, mode="drop"),
            pool_layer.vs.at[blk, :, slot].set(vss, mode="drop"),
        )
    pk, pv = pool_layer
    return (
        pk.at[blk, slot].set(kf.astype(pk.dtype), mode="drop"),
        pv.at[blk, slot].set(vf.astype(pv.dtype), mode="drop"),
    )


def cache_write_token(cache_layer, k, v, pos):
    """Write one new K/V row per lane into a GATHERED contiguous cache.

    ``cache_layer``: (B, L, fused) tuple / QuantKV straight out of
    ``pool_gather``; ``pos``: (B,) int32, each lane's row (its current
    length).  The decode chunk gathers each lane's table ONCE per
    dispatch and then appends rows here — a (B, fused) scatter per step
    instead of re-gathering the whole (B, L, fused) view per layer per
    step.  Row ``pos[b]`` of lane b's gathered view is exactly position
    ``(blk, slot)`` of the pool (`pos = table_index * block_size +
    slot`), so attention over this cache is bit-identical to attention
    over a fresh gather."""
    b = k.shape[0]
    lanes = jnp.arange(b)
    kf = k.reshape(b, -1)
    vf = v.reshape(b, -1)
    if isinstance(cache_layer, QuantKV):
        kq, ks = quantize_q8(k)
        vq, vs = quantize_q8(v)
        return QuantKV(
            cache_layer.kq.at[lanes, pos].set(kq.reshape(b, -1)),
            cache_layer.ks.at[lanes, :, pos].set(
                ks[:, 0, :, 0].astype(cache_layer.ks.dtype)
            ),
            cache_layer.vq.at[lanes, pos].set(vq.reshape(b, -1)),
            cache_layer.vs.at[lanes, :, pos].set(
                vs[:, 0, :, 0].astype(cache_layer.vs.dtype)
            ),
        )
    ck, cv = cache_layer
    return (
        ck.at[lanes, pos].set(kf.astype(ck.dtype)),
        cv.at[lanes, pos].set(vf.astype(cv.dtype)),
    )


def pool_gather(pool_layer, tables):
    """Gather each lane's block table into a contiguous per-lane cache.

    ``tables``: (B, max_blocks) int32 — idle entries use an
    out-of-range id and clip to the last block; the caller's length mask
    never exposes those rows.  Returns the (B, L, fused) tuple / QuantKV
    layout ``ops.quant.kv_attend`` expects, L = max_blocks * block_size.
    """
    b, nmax = tables.shape
    # mode="clip", NOT the jnp.take default "fill": out-of-range ids
    # would otherwise gather NaN rows, and a masked NaN still poisons
    # the softmax output through 0 * NaN on the value side
    if isinstance(pool_layer, QuantKV):
        bs = pool_layer.kq.shape[1]
        hkv = pool_layer.ks.shape[1]

        def rows(x):  # (B, nmax, bs, fused) -> (B, L, fused)
            g = jnp.take(x, tables, axis=0, mode="clip")
            return g.reshape(b, nmax * bs, x.shape[-1])

        def scales(s):  # (B, nmax, Hkv, bs) -> (B, Hkv, L)
            g = jnp.take(s, tables, axis=0, mode="clip")
            return g.transpose(0, 2, 1, 3).reshape(b, hkv, nmax * bs)

        return QuantKV(
            rows(pool_layer.kq), scales(pool_layer.ks),
            rows(pool_layer.vq), scales(pool_layer.vs),
        )
    pk, pv = pool_layer
    bs = pk.shape[1]
    rows = lambda x: jnp.take(x, tables, axis=0, mode="clip").reshape(
        b, nmax * bs, x.shape[-1]
    )
    return (rows(pk), rows(pv))


# pool leaves are (num_blocks, block_size, Hkv*Dh): blocks (the chopped
# sequence dim) over ``seq``, the fused feature dim over ``model``
POOL_SPEC = ("act_seq", None, "act_heads")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKV:
    """One layer's K/V in the paged pool, as a decode chunk holds it: the
    cache object (``infer/kv_cache.py``) of the continuous batch.

    ``pool`` is the layer's block pool, ``view`` each lane's block table
    gathered into a contiguous (B, L, Hkv*Dh) cache (``pool_gather``,
    once a chunk and outside the layer), ``tables`` (B, nmax) the block
    tables and ``lengths`` (B,) the rows each lane already holds.  One
    new token a lane: chunked prefill goes through ``ContiguousKV`` over
    the gathered view."""

    pool: Any
    view: Any
    tables: Any
    lengths: Any

    def positions(self, t: int):
        return self.lengths[:, None] + jnp.arange(t)[None, :]

    def attend(self, q, k, v, *, window: int, core):
        del core  # a prefill's; this cache takes decode steps only
        if q.shape[1] != 1:
            raise ValueError(
                f"the paged cache takes one new token a lane, got {q.shape[1]}"
            )
        pool, tables, lengths = self.pool, self.tables, self.lengths
        bs = pool[0].shape[1]
        nmax = tables.shape[1]
        # each lane's write target; idle lanes carry an out-of-range
        # table entry, so their (garbage) row is dropped by the scatter
        blk = jnp.take_along_axis(
            tables, jnp.minimum(lengths // bs, nmax - 1)[:, None], axis=1
        )[:, 0]
        pool = pool_write_token(pool, k, v, blk, lengths % bs)
        pool = constrain_kv(pool, POOL_SPEC)
        # the same row lands in the chunk's contiguous gathered view:
        # lane b's gathered index (lengths//bs)*bs + lengths%bs ==
        # lengths, so attention here is bit-identical to a fresh gather
        # — without paying the (B, L, fused) gather per layer per step
        # (an idle lane writes row 0 of ITS OWN view: discarded output)
        view = cache_write_token(self.view, k, v, lengths)
        view = constrain_kv(view, CACHE_SPEC)
        key_pos = jnp.arange(nmax * bs)
        # lane b's query sits at position lengths[b] (its row was just
        # written): attend everything at or before it — the identical
        # mask the contiguous decode path builds, per lane
        mask = key_pos[None, None, :] <= lengths[:, None, None]
        if window:
            mask &= key_pos[None, None, :] > lengths[:, None, None] - window
        o = kv_attend(
            q, view, mask, use_kernel=decode_attention_path() == "kernel"
        )
        return o, PagedKV(pool, view, tables, lengths + 1)


def pool_copy_block(pools, src, dst):
    """Copy one block row ``src`` -> ``dst`` across every layer's pool —
    the device half of copy-on-write (a request about to write into a
    block other tables share gets its own bit-identical copy first).
    ``src``/``dst`` are int32 scalars (traced: one compiled program
    serves every copy)."""
    def one(layer):
        cp = lambda x: x.at[dst].set(x[src])
        if isinstance(layer, QuantKV):
            return QuantKV(*(cp(a) for a in layer))
        return tuple(cp(a) for a in layer)

    return tuple(one(layer) for layer in pools)


def apply_block_permutation(pools, plan: dict[int, int], num_blocks: int):
    """Move pool rows per a compaction plan (device-side half of
    ``BlockAllocator.compaction_plan``): new row j reads old row
    ``inverse(j)``; rows not mentioned keep their id."""
    inv = list(range(num_blocks))
    for old, new in plan.items():
        inv[new] = old
    perm = jnp.asarray(inv, jnp.int32)
    take = lambda x: jnp.take(x, perm, axis=0)

    def one(layer):
        if isinstance(layer, QuantKV):
            return QuantKV(*(take(a) for a in layer))
        return tuple(take(a) for a in layer)

    return tuple(one(layer) for layer in pools)
