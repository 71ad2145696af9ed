"""Dense softmax attention — the shared single-device attention kernel.

One implementation used by every caller that needs unsharded attention over
a local block: the transformer's default core (``models/transformer.py``)
and the per-head-group attention inside Ulysses sequence parallelism
(``parallel/ulysses.py``).  Scores masked with -1e30 (not -inf: keeps
fully-masked rows finite), softmax in float32, output back in the compute
dtype — all of it one fused MXU-friendly einsum pair under XLA.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["dense_attention"]


def dense_attention(q, k, v, causal: bool = False, mask=None, window: int = 0):
    """Full softmax attention. q: (B, Tq, H, D), k/v: (B, Tk, Hkv, D) ->
    (B, Tq, H, D) (V's head may have its own width, which the output takes).  ``mask`` is an explicit (Tq, Tk) bool mask (True =
    attend) for cross-length cases like KV-cache decode — or (B, Tq, Tk)
    when every batch row has its own visibility, e.g. the serving
    engine's continuous decode batch where each lane sits at a different
    sequence length (``ddl_tpu/serve/``); ``causal`` builds the square
    tril mask, banded to the last ``window`` positions when ``window > 0``
    (sliding-window attention).

    Grouped-query attention: when ``Hkv < H`` (``H % Hkv == 0``), each K/V
    head serves a group of ``H/Hkv`` query heads.  The grouping is done by
    reshaping the query — the K/V tensors are never materialised at H heads,
    so a (B, L, Hkv, D) decode cache is read as-is at its reduced bandwidth.
    """
    b, tq, h, d = q.shape
    hkv = k.shape[2]
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window and not causal:
        raise ValueError("window > 0 requires causal=True")
    if window and mask is not None:
        # An explicit mask wins over the built-in band; a caller combining
        # both would silently get full-history attention.  Cross-length
        # masks (decode) carry absolute key positions this function cannot
        # see, so the band must be folded into the mask by the caller.
        raise ValueError("pass window via the explicit mask, not both")
    if causal and mask is None:
        mask = jnp.tril(jnp.ones((tq, tq), bool))
        if window:
            # sliding window: row q sees keys in (q - window, q]
            mask &= ~jnp.tril(jnp.ones((tq, tq), bool), -window)
    scale = jnp.sqrt(jnp.asarray(d, q.dtype))
    if hkv == h:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / scale
        if mask is not None:
            # (Tq, Tk) shared across batch, or (B, Tq, Tk) per-lane
            m = mask[None, None] if mask.ndim == 2 else mask[:, None]
            scores = jnp.where(m, scores, -1e30)
        probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if h % hkv:
        raise ValueError(f"q heads {h} must divide by kv heads {hkv}")
    g = h // hkv
    qg = q.reshape(b, tq, hkv, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) / scale
    if mask is not None:
        m = (
            mask[None, None, None] if mask.ndim == 2
            else mask[:, None, None]
        )
        scores = jnp.where(m, scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(q.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(b, tq, h, v.shape[-1])
