"""Pallas TPU selective scan: the Mamba-1 state-space recurrence, fwd + bwd.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * u_t) B_t^T        h_{-1} = 0
    y_t = h_t C_t + D * u_t

``u``, ``dt`` (B, T, d_in); ``A`` (d_in, N), negative; ``B``, ``C``
(B, T, N); ``D`` (d_in,); all float32, and so are the decay and the
state.  The state ``h`` (B, T, d_in, N) is what XLA's lowering of a scan
would keep in HBM (2.7 GB in float32 at B=2, T=4096, d_in=5120, N=16);
here it never leaves VMEM.  The grid is (batch, blocks of d_in, chunks
of time), the chunks walked in order with the block's state (N, block_d)
carried in scratch: N on sublanes, d_in on lanes, so a time step is a
handful of elementwise passes over a few vregs and one sublane reduction
for ``y_t``.  ``B_t`` and ``C_t`` arrive transposed, (N, chunk), so that a
step's column broadcasts along lanes; a chunk's steps are unrolled (static
row and column slices: Mosaic takes no dynamic lane offset).

The forward keeps the state at every chunk's start (B, T / chunk, N,
d_in: 21 MB at the shape above with chunks of 128).  The backward walks
the chunks last to first: it recomputes a chunk's states from its start
into VMEM, then runs the adjoint recurrence back through them, so the
inside of a chunk is never stored.  ``dB_t`` and ``dC_t`` are sums over
d_in: each block of d_in writes its partial and XLA adds the blocks.

The work is the VPU's (about 30 elementwise passes a step and state
element, forward and backward together) and nothing is a matrix product:
the kernels are bound by the vector unit, far under the HBM roofline that
``benchmark/work/sambay.selective_scan_train`` counts.

On the CPU backend the kernels run interpreted (``ops/interpret.py``);
``selective_scan_reference`` is the same recurrence as a ``lax.scan`` over
time, what the tests hold the kernels to.  Nothing falls back to it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.interpret import interpret_default

__all__ = ["selective_scan", "selective_scan_reference", "CHUNK", "BLOCK_D"]

# time steps a grid step (a multiple of 128 on the chip: B^T and C^T blocks
# are (N, chunk) with the chunk on lanes) and the most channels of d_in a
# grid step.  On a v5e at B=2, T=4096, d_in=5120, N=16, ms a call forward /
# forward and backward: blocks of 128 channels 3.67 / 38.5, 256 1.91 / 20.0,
# 512 1.79 / 13.1, 1024 1.86 / 9.1; chunks of 256 at 512 1.82 / 13.0 (PERF.md
# section 6, PR 31).  At 1024 the backward's recomputed states are 8 MiB of
# VMEM; 2048 would pass the default scope.
CHUNK = 128
BLOCK_D = 1024


def _fwd_kernel(u_ref, dt_ref, at_ref, bt_ref, ct_ref, d_ref, y_ref, hs_ref,
                h_sc, *, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_sc[...] = jnp.zeros_like(h_sc)

    h = h_sc[...]
    hs_ref[0, 0] = h
    a, dvec = at_ref[...], d_ref[...]
    for t in range(chunk):
        dt_t, u_t = dt_ref[0, t:t + 1, :], u_ref[0, t:t + 1, :]
        h = jnp.exp(dt_t * a) * h + bt_ref[0, :, t:t + 1] * (dt_t * u_t)
        y_ref[0, t:t + 1, :] = (
            jnp.sum(h * ct_ref[0, :, t:t + 1], axis=0, keepdims=True) + dvec * u_t
        )
    h_sc[...] = h


def _bwd_kernel(u_ref, dt_ref, at_ref, bt_ref, ct_ref, d_ref, hs_ref, dy_ref,
                du_ref, ddt_ref, da_ref, dbt_ref, dct_ref, dh_sc, h_buf, *, chunk):
    @pl.when(pl.program_id(2) == 0)  # the sequence's last chunk
    def _():
        dh_sc[...] = jnp.zeros_like(dh_sc)
        da_ref[...] = jnp.zeros_like(da_ref)

    a, dvec = at_ref[...], d_ref[...]
    # the chunk's states again, from its start: h_buf[t] = h_{t-1}
    h = hs_ref[0, 0]
    for t in range(chunk):
        h_buf[t] = h
        dt_t = dt_ref[0, t:t + 1, :]
        h = jnp.exp(dt_t * a) * h + bt_ref[0, :, t:t + 1] * (dt_t * u_ref[0, t:t + 1, :])
    # the adjoint recurrence, last step first; dh is dL/dh_t
    dh, da = dh_sc[...], da_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, dbt_ref.shape[2:], 1)
    dbt = jnp.zeros(dbt_ref.shape[2:], jnp.float32)
    dct = jnp.zeros(dct_ref.shape[2:], jnp.float32)
    for t in reversed(range(chunk)):
        dt_t, u_t, dy_t = dt_ref[0, t:t + 1, :], u_ref[0, t:t + 1, :], dy_ref[0, t:t + 1, :]
        b_t, c_t = bt_ref[0, :, t:t + 1], ct_ref[0, :, t:t + 1]
        h_prev = h_buf[t]
        decay = jnp.exp(dt_t * a)
        dtu = dt_t * u_t
        dh = dh + c_t * dy_t
        dct = jnp.where(lane == t, jnp.sum(
            (decay * h_prev + b_t * dtu) * dy_t, axis=1, keepdims=True), dct)
        dbt = jnp.where(lane == t, jnp.sum(dh * dtu, axis=1, keepdims=True), dbt)
        g = dh * h_prev * decay  # dL/d(dt_t * A)
        da = da + g * dt_t
        d_dtu = jnp.sum(dh * b_t, axis=0, keepdims=True)
        du_ref[0, t:t + 1, :] = d_dtu * dt_t + dvec * dy_t
        ddt_ref[0, t:t + 1, :] = jnp.sum(g * a, axis=0, keepdims=True) + d_dtu * u_t
        dh = decay * dh
    dh_sc[...] = dh
    da_ref[0] = da
    dbt_ref[0, 0] = dbt
    dct_ref[0, 0] = dct


def _metadata(grid, chunk) -> dict:
    """What ``obs/scope.kernel_tiles`` sums out of the compiled step: the
    call's grid steps and the time steps they walk (``steps / total`` is
    the chunk's length)."""
    total = grid[0] * grid[1] * grid[2]
    return {"tiles_total": str(total), "tiles_steps": str(total * chunk)}


def _specs(n, chunk, block_d, time_of):
    """Block specs shared by both kernels; ``time_of(c)`` is the chunk a
    grid step works on (the backward walks them last to first)."""
    row = pl.BlockSpec((1, chunk, block_d), lambda b, j, c: (b, time_of(c), j))
    col = pl.BlockSpec((1, n, chunk), lambda b, j, c: (b, 0, time_of(c)))
    at = pl.BlockSpec((n, block_d), lambda b, j, c: (0, j))
    dvec = pl.BlockSpec((1, block_d), lambda b, j, c: (0, j))
    hs = pl.BlockSpec((1, 1, n, block_d), lambda b, j, c: (b, time_of(c), 0, j))
    return row, col, at, dvec, hs


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _scan_fwd(u, dt, at, bt, ct, dvec, chunk, block_d, interpret):
    b, t, d_in = u.shape
    n = at.shape[0]
    grid = (b, d_in // block_d, t // chunk)
    row, col, a_spec, d_spec, hs = _specs(n, chunk, block_d, lambda c: c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        out_shape=(
            jax.ShapeDtypeStruct((b, t, d_in), jnp.float32),
            jax.ShapeDtypeStruct((b, t // chunk, n, d_in), jnp.float32),
        ),
        grid=grid,
        in_specs=[row, row, a_spec, col, col, d_spec],
        out_specs=(row, hs),
        scratch_shapes=[pltpu.VMEM((n, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssm_scan_fwd",
        metadata=_metadata(grid, chunk),
    )(u, dt, at, bt, ct, dvec)


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _scan_bwd(u, dt, at, bt, ct, dvec, hs, dy, chunk, block_d, interpret):
    b, t, d_in = u.shape
    n = at.shape[0]
    nd, nc = d_in // block_d, t // chunk
    grid = (b, nd, nc)
    row, col, a_spec, d_spec, hs_spec = _specs(n, chunk, block_d, lambda c: nc - 1 - c)
    partial_col = pl.BlockSpec((1, 1, n, chunk), lambda b, j, c: (b, j, 0, nc - 1 - c))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        out_shape=(
            jax.ShapeDtypeStruct((b, t, d_in), jnp.float32),       # du
            jax.ShapeDtypeStruct((b, t, d_in), jnp.float32),       # ddt
            jax.ShapeDtypeStruct((b, n, d_in), jnp.float32),       # dA^T a batch row
            jax.ShapeDtypeStruct((b, nd, n, t), jnp.float32),      # dB^T a block of d_in
            jax.ShapeDtypeStruct((b, nd, n, t), jnp.float32),      # dC^T
        ),
        grid=grid,
        in_specs=[row, row, a_spec, col, col, d_spec, hs_spec, row],
        out_specs=(
            row, row,
            pl.BlockSpec((1, n, block_d), lambda b, j, c: (b, 0, j)),
            partial_col, partial_col,
        ),
        scratch_shapes=[
            pltpu.VMEM((n, block_d), jnp.float32),
            pltpu.VMEM((chunk, n, block_d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssm_scan_bwd",
        metadata=_metadata(grid, chunk),
    )(u, dt, at, bt, ct, dvec, hs, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(u, dt, at, bt, ct, dvec, chunk, block_d, interpret):
    return _scan_fwd(u, dt, at, bt, ct, dvec, chunk, block_d, interpret)


def _scan_vjp_fwd(u, dt, at, bt, ct, dvec, chunk, block_d, interpret):
    y, hs = _scan_fwd(u, dt, at, bt, ct, dvec, chunk, block_d, interpret)
    return (y, hs), (u, dt, at, bt, ct, dvec, hs)


def _scan_vjp_bwd(chunk, block_d, interpret, res, cot):
    u, dt, at, bt, ct, dvec, hs = res
    dy, _ = cot  # the boundary states are read, never differentiated
    du, ddt, da, dbt, dct = _scan_bwd(
        u, dt, at, bt, ct, dvec, hs, dy, chunk, block_d, interpret
    )
    return (du, ddt, da.sum(0), dbt.sum(1), dct.sum(1),
            jnp.sum(dy * u, axis=(0, 1))[None])


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def selective_scan(u, dt, a, bm, cm, dvec, *, chunk: int = CHUNK,
                   block_d: int = BLOCK_D, interpret: bool | None = None):
    """``(y, state_absmax)``: the scan's output (B, T, d_in) float32 and
    the largest ``|h|`` at a chunk's start (a scalar, not differentiated).

    T is padded to a multiple of ``chunk`` (a sequence shorter than one
    chunk to a multiple of 8, and is one chunk) with steps of ``dt = 0``
    (the state passes through them unchanged); ``block_d`` is halved until it
    divides ``d_in`` (on the chip a block is a multiple of 128 lanes or
    all of ``d_in``).  Differentiable in every argument (custom VJP, the
    backward kernel)."""
    if interpret is None:
        interpret = interpret_default()
    b, t, d_in = u.shape
    block_d = min(block_d, d_in)
    while d_in % block_d:
        block_d //= 2
    chunk = min(chunk, -(-t // 8) * 8)  # a short sequence is one chunk
    pad = -t % chunk
    f32 = jnp.float32
    rows = [jnp.pad(x.astype(f32), ((0, 0), (0, pad), (0, 0))) for x in (u, dt, bm, cm)]
    y, hs = _scan(
        rows[0], rows[1], a.astype(f32).T,
        rows[2].transpose(0, 2, 1), rows[3].transpose(0, 2, 1),
        dvec.astype(f32)[None], chunk, block_d, interpret,
    )
    return y[:, :t], jax.lax.stop_gradient(jnp.max(jnp.abs(hs)))


def selective_scan_reference(u, dt, a, bm, cm, dvec):
    """The same recurrence as one ``lax.scan`` over time in float32:
    ``(y, state_absmax)`` with the largest ``|h|`` over every step."""
    f32 = jnp.float32
    u, dt, bm, cm = (jnp.moveaxis(x.astype(f32), 1, 0) for x in (u, dt, bm, cm))
    a, dvec = a.astype(f32), dvec.astype(f32)

    def step(carry, xs):
        h, top = carry
        u_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[..., None] * a) * h + (dt_t * u_t)[..., None] * b_t[:, None, :]
        y = jnp.sum(h * c_t[:, None, :], axis=-1) + dvec * u_t
        return (h, jnp.maximum(top, jnp.max(jnp.abs(h)))), y

    h0 = jnp.zeros((u.shape[1], u.shape[2], a.shape[1]), f32)
    (_, top), y = jax.lax.scan(step, (h0, jnp.zeros((), f32)), (u, dt, bm, cm))
    return jnp.moveaxis(y, 0, 1), jax.lax.stop_gradient(top)
