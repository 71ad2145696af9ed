"""Where a layer's K and V live during incremental decode.

``models.transformer.Attention`` projects, normalises and rotates; what
happens to the new K/V after that is a cache object's business.  A cache
is a pytree (it rides ``jit`` and ``lax.scan`` carries) that answers
three questions and hides everything else about its format:

* ``positions(t)``: the absolute positions of the ``t`` new tokens;
* ``attend(q, k, v, *, window, core)`` -> ``(o, new_cache)``: write the
  new rows where this cache keeps them, choose which rows the queries
  read and under what mask, and decide between a prefill through
  ``core`` (a training-style causal forward over fresh K/V) and cached
  attention (``ops.quant.kv_attend``), with or without the one-pass
  decode kernel;
* its leaves' sharding constraints (``constrain_kv``).

Two kinds live here: ``ContiguousKV`` (a linear ``(B, L, Hkv*Dh)``
buffer) and ``RollingKV`` (a ring of capacity ``attn_window``).  The
third, ``serve.kv_pool.PagedKV``, lives beside the pool it writes.
Leaves are a ``(k, v)`` tuple or an ``ops.quant.QuantKV`` throughout.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ddl_tpu.models.transformer import LMConfig, _ambient_mesh_shape
from ddl_tpu.ops.quant import (
    QuantKV,
    kv_attend,
    kv_set_slots,
    kv_slice,
    kv_write,
)

__all__ = [
    "CACHE_SPEC",
    "ContiguousKV",
    "RollingKV",
    "constrain_kv",
    "decode_attention_path",
    "init_kv_cache",
    "zeros_kv",
]

# fused-storage cache leaves are 3-D (B, L, Hkv*Dh) (ops/quant.kv_fuse)
CACHE_SPEC = ("batch", "act_seq", "act_heads")


def _ambient_mesh_size() -> int:
    """Device count of the ambient mesh — 1 without a mesh context."""
    return math.prod(int(n) for n in _ambient_mesh_shape().values())


def decode_attention_path(mesh_size: int | None = None) -> str:
    """Which cached-attention path a decode program over a mesh of
    ``mesh_size`` devices (None = the ambient mesh of the trace) takes:
    ``"kernel"`` (the one-pass Pallas kernel, ``ops/decode_attention.py``)
    or ``"einsum"``.  The one gate every cache kind reads.  The kernel
    only where it is a real kernel: on the CPU backend it would run
    interpreted (orders of magnitude slower than the einsum), and the
    CPU einsum path is also what keeps serve tokens bit-identical to the
    sequential einsum reference.  GSPMD cannot partition a custom call,
    so any mesh larger than one device keeps the einsum too — a known
    limit (ROADMAP S6), reported in ``ServeEngine.stats`` rather than
    taken in silence."""
    if mesh_size is None:
        mesh_size = _ambient_mesh_size()
    if mesh_size == 1 and jax.default_backend() == "tpu":
        return "kernel"
    return "einsum"


def constrain_kv(kv, spec):
    """Sharding-constrain cache or pool leaves — SKIPPED on a trivial
    mesh.  The constraint lowers to a sharding custom-call between the
    cache update and its consumers; on one device it is semantically a
    no-op but BREAKS XLA's while-loop in-place aliasing, so every decode
    step copied the whole cache: profiled at B=32/T=768, the 24
    dynamic-update-slices cost ~27 us each (full-buffer copy speed) plus
    ~0.7 ms/step of explicit copies — the majority of decode time
    (bench/profile_decode.py, PERF.md round 5).  Multi-device decode
    keeps the constraints (the cache's model/seq sharding needs them).

    ``spec`` is the K/V leaves' (``CACHE_SPEC`` for a (B, L, Hkv*Dh)
    cache, ``kv_pool.POOL_SPEC`` for the pool's blocks); QuantKV scale
    leaves keep the sequence dim LAST, so their spec transposes the last
    two axes."""
    if _ambient_mesh_size() <= 1:
        return kv
    c = nn.with_logical_constraint
    if isinstance(kv, QuantKV):
        sspec = (spec[0], spec[2], spec[1])
        return QuantKV(
            c(kv.kq, spec), c(kv.ks, sspec), c(kv.vq, spec), c(kv.vs, sspec)
        )
    return tuple(c(a, spec) for a in kv)


@dataclasses.dataclass
class _LinearKV:
    kv: Any  # (k, v) or QuantKV, leaves (B, L, Hkv*Dh)
    offset: Any  # positions already held: a Python int or a traced scalar

    def positions(self, t: int):
        return self.offset + jnp.arange(t)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ContiguousKV(_LinearKV):
    """A linear buffer: row ``p`` holds position ``p``; ``offset`` rows
    are filled."""

    def attend(self, q, k, v, *, window: int, core):
        t, offset = q.shape[1], self.offset
        kv = constrain_kv(kv_write(self.kv, k, v, offset), CACHE_SPEC)
        new = ContiguousKV(kv, offset + t)
        if t > 1 and isinstance(offset, int) and offset == 0:
            # prefill: the cache holds nothing older than these tokens, so
            # attend the fresh K/V directly — causal (+window) over the
            # prompt, optionally through the flash kernel — instead of
            # masked-attending the whole allocated buffer.  Scores are
            # O(T^2) (O(T*W) windowed / O(T*block) flash) rather than
            # O(T*capacity): a B=8, T=4096 prefill against an 8K cache
            # would otherwise materialise a 13 GB score tensor and OOM.
            return core(q, k, v), new
        # queries at global positions offset+i attend keys <= that
        # position; padded cache slots beyond offset+t are masked out.
        q_pos = (offset + jnp.arange(t))[:, None]
        cap = span = kv[0].shape[1]
        read, start = kv, 0
        if window and window + t - 1 < cap:
            # windowed decode reads an O(window) slice, not the whole
            # cache: the span (window + t - 1) covers every key any of
            # the t queries can see, and the positional mask below
            # handles the clamped warm-up region exactly.
            span = window + t - 1
            start = jnp.clip(offset + t - span, 0, cap - span)
            read = kv_slice(kv, start, span)
        key_pos = start + jnp.arange(span)
        mask = key_pos[None, :] <= q_pos  # (T, span)
        if window:
            mask &= key_pos[None, :] > q_pos - window
        o = kv_attend(
            q, read, mask,
            # the one-pass kernel attends the FULL buffer; a windowed
            # O(span) slice keeps the einsum path
            use_kernel=(
                t == 1 and span == cap
                and decode_attention_path() == "kernel"
            ),
        )
        return o, new


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RollingKV(_LinearKV):
    """A RING of capacity ``attn_window``: slot ``p % L`` holds position
    ``p``, so allocation is O(window) no matter how long the generation
    runs — the memory-side twin of the linear cache's O(window) read
    slice.  Prefill (``t > 1``) attends its own fresh K/V directly
    (banded causal — the ring holds nothing older) and writes only the
    last ``min(L, t)`` keys; single-token decode writes one slot and
    reads the whole ring under a derived absolute-position mask."""

    def attend(self, q, k, v, *, window: int, core):
        if not window:
            raise ValueError("rolling decode cache requires attn_window")
        t, offset = q.shape[1], self.offset
        cap = self.kv[0].shape[1]
        if t > 1:
            o = core(q, k, v)
            keep = min(cap, t)
            slots = (offset + t - keep + jnp.arange(keep)) % cap
            kv = kv_set_slots(self.kv, k[:, -keep:], v[:, -keep:], slots)
        else:
            kv = kv_write(self.kv, k, v, offset % cap)
            # slot s holds the newest position congruent to s (mod
            # cap); never-written slots derive negative positions
            key_pos = offset - ((offset - jnp.arange(cap)) % cap)
            mask = (
                (key_pos[None, :] <= offset)
                & (key_pos[None, :] > offset - window)
                & (key_pos[None, :] >= 0)
            )
            o = kv_attend(
                q, kv, mask,
                use_kernel=decode_attention_path() == "kernel",
            )
        return o, RollingKV(constrain_kv(kv, CACHE_SPEC), offset + t)


def init_kv_cache(
    cfg: LMConfig, batch: int, max_len: int, dtype=None,
    rolling: bool = False, quant: bool = False,
) -> tuple:
    """Per-layer empty caches over zeroed (B, L, Hkv*Dh) buffers:
    ``ContiguousKV`` of ``L = max_len`` rows, or with ``rolling=True``
    ``RollingKV`` of ``min(max_len, attn_window)`` — the ring cache holds
    only the window, so a windowed generation's cache memory is
    O(window) regardless of ``max_len``.

    With grouped-query attention (``cfg.n_kv_heads``) the cache holds only
    the K/V heads — an ``n_heads/n_kv_heads``-times smaller buffer, which
    is GQA's decode-bandwidth win (the grouped ``dense_attention`` reads it
    without re-materialising full heads).

    ``quant=True`` allocates ``ops.quant.QuantKV`` leaves instead: int8
    K/V plus per-(token, head) f32 scales — ~0.53x the bf16 bytes, the
    KV half of the int8 serving path (attention quantizes on write and
    reads the int8 buffers directly)."""
    if rolling and not cfg.attn_window:
        raise ValueError("rolling cache requires cfg.attn_window > 0")
    length = min(max_len, cfg.attn_window) if rolling else max_len
    kind = RollingKV if rolling else ContiguousKV
    kv = zeros_kv(cfg, batch, length, dtype, quant)
    return tuple(kind(kv, 0) for _ in range(cfg.n_layers))


def zeros_kv(cfg: LMConfig, lead: int, rows: int, dtype, quant: bool):
    """One layer's zeroed K/V storage, ``(lead, rows, Hkv*Dh)`` leaves:
    ``lead`` is the batch and ``rows`` the length for a cache, the blocks
    and the block size for the pool — the one storage format of both."""
    if quant and dtype is not None:
        raise ValueError(
            "quant=True fixes the layout (int8 + f32 scales); "
            "dtype cannot be combined with it"
        )
    # storage fuses (Hkv, Dh) -> Hkv*Dh so XLA's layout keeps the feature
    # dim in lanes and the per-token cache write is in place
    # (ops/quant.kv_fuse); readers unfuse at the attention einsum
    shape = (lead, rows, cfg.kv_heads * cfg.head_dim)
    if quant:
        q = jnp.zeros(shape, jnp.int8)
        # scales keep the rows minor: the decode kernel reads one aligned
        # (L,) lane vector per head (ops/quant.QuantKV)
        s = jnp.zeros((lead, cfg.kv_heads, rows), jnp.float32)
        return QuantKV(q, s, q, s)
    zero = jnp.zeros(shape, dtype or cfg.dtype)
    return (zero, zero)
