"""Pallas int8 weight-streaming matmul for tiny-M decode steps.

The B=1 decode profile (`bench/profile_decode.py --batch 1 --quant
kv+w`, PERF.md round 5) showed XLA lowering the int8 weight matmuls to
VPU ``multiply_reduce`` fusions running at ~440 GB/s — about half the
HBM peak — which is why int8 weights bought only +29% at B=1 against a
~2x byte ratio.  This kernel streams the int8 weight through the MXU
instead: the activation is zero-padded to M=8 rows (MXU throughput for
a weight-stationary stream is bandwidth-bound, not M-bound), the weight
arrives in (D, block_o) tiles converted to bf16 in VMEM, and the
per-output-channel scale applies to the (8, block_o) product.

Status: MEASURED SLOWER and therefore NOT wired into the model — the
committed negative result (PERF.md round 5).  Integrated into
QDense/LMHead and A/B'd on chip at B=1 GQA+window kv+w: 3007 tok/s
(XLA multiply-reduce) vs 2153 (block_o=512) / 2360 (block_o=2048) with
this kernel — the per-call overhead of ~84 extra pallas launches per
decode step and the M=8 padding outweigh whatever stream-rate advantage
the MXU path has.  The kernel and its parity tests stay as the
experiment record until ROADMAP D4 takes them; the next attempt at this
lever should fuse the matvec with its neighbours instead of replacing
one op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ddl_tpu.ops.interpret import interpret_default

__all__ = ["MATVEC_MAX_ROWS", "int8_matmul_small_m"]

MATVEC_MAX_ROWS = 8
_BLOCK_O = 2048


def _kernel(x_ref, w_ref, s_ref, o_ref, *, contract_last: bool):
    x = x_ref[...]  # (8, D), the caller's compute dtype
    w = w_ref[...].astype(x.dtype)  # int8 -> exact in bf16 and f32
    dims = (((1,), (1,)), ((), ())) if contract_last else (
        ((1,), (0,)), ((), ()))
    y = jax.lax.dot_general(
        x, w, dims, preferred_element_type=jnp.float32
    )  # (8, bo)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("contract_last", "block_o", "interpret")
)
def int8_matmul_small_m(x, w8, scale, *, contract_last: bool = False,
                        block_o: int = _BLOCK_O, interpret=None):
    """``(x @ dequant(w8)) * scale`` for M ≤ 8 activation rows.

    x: (M, D) with M ≤ 8; ``w8`` int8, either (D, O) (``contract_last=
    False`` — the ``QDense`` kernel layout) or (O, D) (``True`` — the
    vocab-major ``LMHead`` layout); ``scale`` with exactly O elements
    (any shape).  ``block_o`` must be a multiple of 128 (Mosaic lane
    rule).  Returns (M, O) f32-accumulated in x.dtype (f32 in, f32 out
    for the head).
    """
    m, d = x.shape
    if m > MATVEC_MAX_ROWS:
        raise ValueError(f"M={m} > {MATVEC_MAX_ROWS}; use the XLA path")
    if block_o % 128:
        raise ValueError(f"block_o {block_o} must be a multiple of 128")
    o = w8.shape[0] if contract_last else w8.shape[1]
    # keep the O block 128-lane/8-sublane aligned (Mosaic block rules)
    # by zero-padding O up to a block multiple instead of shrinking bo
    bo = min(block_o, o + (-o) % 128)
    o_pad = o + (-o) % bo
    if o_pad != o:
        pad = [(0, o_pad - o), (0, 0)] if contract_last else \
            [(0, 0), (0, o_pad - o)]
        w8 = jnp.pad(w8, pad)
    if interpret is None:
        interpret = interpret_default()
    xp = jnp.zeros((MATVEC_MAX_ROWS, d), x.dtype).at[:m].set(x)
    s_row = jnp.pad(
        jnp.broadcast_to(scale.reshape(1, o), (1, o)),
        [(0, 0), (0, o_pad - o)],
    )
    w_spec = (
        pl.BlockSpec((bo, d), lambda i: (i, 0))
        if contract_last
        else pl.BlockSpec((d, bo), lambda i: (0, i))
    )
    out = pl.pallas_call(
        functools.partial(_kernel, contract_last=contract_last),
        grid=(o_pad // bo,),
        in_specs=[
            pl.BlockSpec((MATVEC_MAX_ROWS, d), lambda i: (0, 0)),
            w_spec,
            pl.BlockSpec((1, bo), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((MATVEC_MAX_ROWS, bo), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((MATVEC_MAX_ROWS, o_pad), x.dtype),
        interpret=interpret,
        name="int8_matvec",
    )(xp, w8, s_row)
    return out[:m, :o]
