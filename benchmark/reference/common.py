"""What both plain references share: precisions, Adam, and the three
checked steps.  Straight ``jax.numpy``; nothing here (or in the family
files beside it) imports the program or takes anything it has made.

A reference family module provides

    init_params(key, model) -> {name: f32 array}      the cell's weights
    loss_fn(params, batch, model, cast) -> scalar     mean loss over rows
    rows(batch) -> int                                 rows in a batch

and ``three_steps`` below drives Adam over them.  ``precision``:

    "f32"       float32 operands at ``Precision.HIGHEST`` (the reference)
    "bf16"      bfloat16 operands, f32 accumulation (what the cells state)
    "fp8"       operands rounded to float8 e4m3 going forward, cotangents to
                e5m2 going back (the control: the precision below bf16)
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

__all__ = ["caster", "adam_update", "leaf_norms", "three_steps", "PRECISIONS"]

PRECISIONS = ("f32", "bf16", "fp8")


def _rounder(dtype, clip=None):
    def cast(x):
        x = x.astype(jnp.float32)
        if clip is not None:
            x = jnp.clip(x, -clip, clip)
        return x.astype(dtype).astype(jnp.float32)
    return cast


def _round_cotangent(cast):
    """Identity forward; in the backward pass the cotangent is rounded by
    ``cast``: a lower-precision path rounds what flows back as well."""
    @jax.custom_vjp
    def f(y):
        return y

    f.defvjp(lambda y: (y, None), lambda _, g: (cast(g),))
    return f


def caster(precision: str):
    """``(cast, lax_precision, round_back)``: how matmul/conv operands are
    rounded going forward and the products' cotangents going back.  The
    rounded values return to float32 and every product is taken at
    ``Precision.HIGHEST``: exactly a low-precision operand pair with f32
    accumulation, and a backward pass that needs no mixed-type kernels.
    ``fp8`` is the usual recipe of the precision below bf16: e4m3 forward
    (clipped to its range), e5m2 backward, no scaling."""
    highest = jax.lax.Precision.HIGHEST
    if precision == "f32":
        return (lambda x: x.astype(jnp.float32)), highest, (lambda y: y)
    if precision == "bf16":
        cast = _rounder(jnp.bfloat16)
        return cast, highest, _round_cotangent(cast)
    if precision == "fp8":
        return (_rounder(jnp.float8_e4m3fn, clip=448.0), highest,
                _round_cotangent(_rounder(jnp.float8_e5m2, clip=57344.0)))
    raise ValueError(f"unknown precision {precision!r}; have {PRECISIONS}")


def adam_update(params, grads, mu, nu, t, opt):
    """Plain Adam (Kingma & Ba; eps outside the root, as torch and optax)."""
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["learning_rate"]
    out_p, out_m, out_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        m = b1 * mu[k] + (1.0 - b1) * g
        v = b2 * nu[k] + (1.0 - b2) * g * g
        mhat = m / (1.0 - b1**t)
        vhat = v / (1.0 - b2**t)
        out_p[k] = p - lr * mhat / (jnp.sqrt(vhat) + eps)
        out_m[k], out_v[k] = m, v
    return out_p, out_m, out_v


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


_PROGRAMS: dict = {}


def _programs(family, model: dict, opt: dict, precision: str, row_block: int):
    """The jitted step and change-norm programs, built once per
    (family, sizes, optimizer, precision): a calibration reads a dozen
    seeds in one process."""
    import json

    key = (family.__name__, json.dumps(model, sort_keys=True),
           json.dumps(opt, sort_keys=True), precision, row_block)
    if key in _PROGRAMS:
        return _PROGRAMS[key]
    grad_fn = family.make_grad_fn(model, caster(precision), row_block)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, t, batch):
        loss, grads = grad_fn(params, batch)
        gn = leaf_norms(grads)
        params, mu, nu = adam_update(params, grads, mu, nu, t, opt)
        return params, mu, nu, loss, gn

    @jax.jit
    def delta(params, start):
        return leaf_norms({k: params[k] - start[k] for k in params})

    _PROGRAMS[key] = (step, delta)
    return step, delta


def three_steps(family, model: dict, opt: dict, params0: dict, batches, *,
                precision: str = "f32", row_block: int = 0, steps: int = 3,
                half_batch: bool = False) -> dict:
    """Follow the first ``steps`` optimizer steps from ``params0`` over
    ``batches`` (one per step).  Returns host floats:

        losses        [loss of each step, before its update]
        grad_norms    {leaf: ||g_1||}, the first gradient as Adam gets it
        delta_norms   {leaf: ||theta_steps - theta_0||}

    ``half_batch`` plants the fault "half of the batch left out, the mean
    taken over the rest" (only ever used to read that fault's numbers).
    """
    step, delta = _programs(family, model, opt, precision, row_block)

    start = {k: jnp.array(v, jnp.float32) for k, v in params0.items()}
    params = {k: jnp.array(v, jnp.float32) for k, v in params0.items()}
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, grad_norms, seconds = [], None, []
    for i in range(steps):
        t0 = time.perf_counter()
        batch = batches[i]
        if half_batch:
            batch = tuple(b[: family.rows(batch) // 2] for b in batch)
        params, mu, nu, loss, gn = step(params, mu, nu, jnp.float32(i + 1), batch)
        losses.append(float(loss))
        seconds.append(time.perf_counter() - t0)
        if i == 0:
            grad_norms = {k: float(v) for k, v in gn.items()}
    delta_norms = {k: float(v) for k, v in delta(params, start).items()}
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta_norms,
            "step_seconds": seconds}
