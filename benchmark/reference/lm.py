"""Plain reference of the LM configurations' block at any widths.

Decoder-only, pre-RMSNorm (eps 1e-6, f32), rotary positions (half-split,
theta from the configuration), causal softmax attention scaled by
1/sqrt(head_dim), tanh-GELU two-matrix MLP, no biases, final RMSNorm, an
untied (vocab, d_model) output head, mean next-token cross-entropy in
f32.  No kernels, no cache, no batching tricks; rows go through in blocks
(``row_block``) and layers through one scanned, checkpointed body so the
published size fits beside nothing else on one chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["init_params", "make_grad_fn", "rows", "leaf_names"]

_BLOCK_LEAVES = (
    "norm_attn/scale", "attn/q/kernel", "attn/k/kernel", "attn/v/kernel",
    "attn/out/kernel", "norm_mlp/scale", "mlp/wi/kernel", "mlp/wo/kernel",
)


def _shapes(model: dict) -> dict:
    d, h, dh, f, v = (model["d_model"], model["n_heads"], model["head_dim"],
                      model["d_ff"], model["vocab_size"])
    shapes = {"embed/embedding": (v, d)}
    per = {
        "norm_attn/scale": (d,), "attn/q/kernel": (d, h * dh),
        "attn/k/kernel": (d, h * dh), "attn/v/kernel": (d, h * dh),
        "attn/out/kernel": (h * dh, d), "norm_mlp/scale": (d,),
        "mlp/wi/kernel": (d, f), "mlp/wo/kernel": (f, d),
    }
    for i in range(model["n_layers"]):
        for name, shape in per.items():
            shapes[f"block{i}/{name}"] = shape
    shapes["norm_f/scale"] = (d,)
    shapes["lm_head/kernel"] = (v, d)
    return shapes


def leaf_names(model: dict) -> list[str]:
    return list(_shapes(model))


def init_params(key, model: dict) -> dict:
    """The cell's weights from the seed's key: matrices normal with
    std 1/sqrt(fan_in), the embedding 0.02, norm scales 1 + 0.1 n."""
    out = {}
    for i, (name, shape) in enumerate(_shapes(model).items()):
        k = jax.random.fold_in(key, i)
        n = jax.random.normal(k, shape, jnp.float32)
        if name.endswith("scale"):
            out[name] = 1.0 + 0.1 * n
        elif name == "embed/embedding":
            out[name] = 0.02 * n
        elif name == "lm_head/kernel":
            out[name] = n / jnp.sqrt(jnp.float32(shape[1]))
        else:
            out[name] = n / jnp.sqrt(jnp.float32(shape[0]))
    return out


def rows(batch) -> int:
    return int(batch[0].shape[0])


def _rms(x, scale):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * scale


def _rope(x, theta):
    t, dh = x.shape[1], x.shape[3]
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _forward_loss(params, inputs, targets, model, precision):
    cast, prec, round_back = precision
    h_n, dh, theta = model["n_heads"], model["head_dim"], model.get("rope_theta", 10000.0)

    def mm(eq, a, b):
        return round_back(jnp.einsum(eq, cast(a), cast(b), precision=prec,
                                     preferred_element_type=jnp.float32))

    x = jnp.take(params["embed/embedding"], inputs, axis=0)
    b, t, _ = x.shape
    mask = jnp.tril(jnp.ones((t, t), bool))
    stacked = {
        name: jnp.stack([params[f"block{i}/{name}"] for i in range(model["n_layers"])])
        for name in _BLOCK_LEAVES
    }

    @jax.checkpoint
    def block(x, p):
        h = _rms(x, p["norm_attn/scale"])
        q = mm("btd,de->bte", h, p["attn/q/kernel"]).reshape(b, t, h_n, dh)
        k = mm("btd,de->bte", h, p["attn/k/kernel"]).reshape(b, t, h_n, dh)
        v = mm("btd,de->bte", h, p["attn/v/kernel"]).reshape(b, t, h_n, dh)
        q, k = _rope(q, theta), _rope(k, theta)
        s = mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dh))
        s = jnp.where(mask[None, None], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o = mm("bhqk,bkhd->bqhd", pr, v).reshape(b, t, h_n * dh)
        x = x + mm("bte,ed->btd", o, p["attn/out/kernel"])
        h = _rms(x, p["norm_mlp/scale"])
        u = jax.nn.gelu(mm("btd,df->btf", h, p["mlp/wi/kernel"]), approximate=True)
        return x + mm("btf,fd->btd", u, p["mlp/wo/kernel"]), None

    x, _ = jax.lax.scan(block, x, stacked)
    x = _rms(x, params["norm_f/scale"])
    logits = mm("btd,vd->btv", x, params["lm_head/kernel"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


def make_grad_fn(model: dict, precision, row_block: int = 0):
    """``(params, (inputs, targets)) -> (mean loss, grads)``; rows go
    through ``row_block`` at a time and the sums are divided once."""

    def grad_fn(params, batch):
        inputs, targets = (jnp.asarray(a, jnp.int32) for a in batch)
        n, t = inputs.shape
        rb = row_block if row_block and n % row_block == 0 else n
        vg = jax.value_and_grad(
            lambda p, i, tg: _forward_loss(p, i, tg, model, precision)
        )
        zeros = jax.tree.map(jnp.zeros_like, params)

        def body(carry, blk):
            loss, grads = carry
            l, g = vg(params, blk[0], blk[1])
            return (loss + l, jax.tree.map(jnp.add, grads, g)), None

        blocks = (inputs.reshape(n // rb, rb, t), targets.reshape(n // rb, rb, t))
        (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zeros), blocks)
        scale = 1.0 / (n * t)
        return loss * scale, jax.tree.map(lambda g: g * scale, grads)

    return grad_fn
