"""Plain reference of the afmoe block (Trinity-Mini's family) at any widths.

The equations, with the configuration's keys in brackets; what the keys
do not say follows the family's public modelling code (``transformers``
``models/afmoe/modeling_afmoe.py``) and is listed under ``assumed`` in the
configuration file.  No biases anywhere; RMSNorm in float32, eps
[``rms_norm_eps``].

    h0 = E[tokens] * sqrt(d)                                [mup_enabled]
    block:  a = RMSNorm_in(x)
            q = RMSNorm_q(heads(a Wq)), k = RMSNorm_k(heads(a Wk))   over each head's head_dim
            v = heads(a Wv), g = a Wg
            a sliding_attention layer rotates q and k (half-split rotary,
            theta [rope_theta]) and sees keys i - window < j <= i
            [sliding_window]; a full_attention layer rotates nothing and
            sees j <= i                                     [layer_types]
            attn = ((softmax(q k^T / sqrt(head_dim)) v) * sigmoid(g)) Wo
            x = x + RMSNorm_post_attn(attn)
            m = RMSNorm_pre_mlp(x);  x = x + RMSNorm_post_mlp(F(m))
    F, leading dense layers [num_dense_layers]:
            (silu(m Wgate) * (m Wup)) Wdown
    F, expert layers:
            s = sigmoid(m Wr)                               [score_func]
            S = top-k of (s + b), b not trained             [num_experts_per_tok]
            w_e = route_scale * s_e / (sum_{e' in S} s_e' + 1e-20)   [route_norm, route_scale]
            F(m) = Shared(m) + sum_{e in S, e held here} w_e Expert_e(m)
    logits = RMSNorm_f(h_L) W_head; mean next-token cross-entropy in f32.

No kernels, no sort, no cache: a loop over the held experts with masks,
and attention in blocks over K/V heads and query rows (one head's
4096 x 4096 float32 scores are 67 MB; 32 heads with their gradients do
not fit beside the published-width parameters, their gradients and Adam's
moments otherwise).  Each residual branch of a layer is one
``jax.checkpoint``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["init_params", "make_grad_fn", "rows", "leaf_names", "forward_logits"]

# query rows an attention block: (2, 8, 512, 4096) float32 scores are 134 MB
_Q_BLOCK = 512
# token rows a block of the head and the loss
_LOSS_BLOCK = 2048


def _shapes(model: dict) -> dict:
    d, h, hkv, dh = model["d_model"], model["n_heads"], model["n_kv_heads"], model["head_dim"]
    v, f, fe = model["vocab_size"], model["d_ff"], model["moe_d_ff"]
    e, held = model["num_experts"], model["experts_held"]
    shapes = {"embed/embedding": (v, d)}
    for i in range(model["n_layers"]):
        per = {
            "norm_attn/scale": (d,), "attn/q/kernel": (d, h * dh),
            "attn/k/kernel": (d, hkv * dh), "attn/v/kernel": (d, hkv * dh),
            "attn/gate/kernel": (d, h * dh), "attn/q_norm/scale": (dh,),
            "attn/k_norm/scale": (dh,), "attn/out/kernel": (h * dh, d),
            "norm_post_attn/scale": (d,), "norm_mlp/scale": (d,),
            "norm_post_mlp/scale": (d,),
        }
        if i < model["num_dense_layers"]:
            per.update({"mlp/wg/kernel": (d, f), "mlp/wi/kernel": (d, f),
                        "mlp/wo/kernel": (f, d)})
        else:
            fs = fe * model["num_shared_experts"]
            per.update({
                "moe/router/kernel": (d, e), "moe/bias": (e,),
                "moe/wg": (held, d, fe), "moe/wi": (held, d, fe), "moe/wo": (held, fe, d),
                "moe/shared/wg/kernel": (d, fs), "moe/shared/wi/kernel": (d, fs),
                "moe/shared/wo/kernel": (fs, d),
            })
        for name, shape in per.items():
            shapes[f"block{i}/{name}"] = shape
    shapes["norm_f/scale"] = (d,)
    shapes["lm_head/kernel"] = (v, d)
    return shapes


def leaf_names(model: dict) -> list[str]:
    return list(_shapes(model))


def init_params(key, model: dict) -> dict:
    """The cell's weights from the seed's key: matrices normal with std
    1/sqrt(fan_in), the embedding 0.02, norm scales 1 + 0.1 n, and the
    selection bias ``b`` 0.02 n: not zero, so that a program that weights
    by ``s + b``, or leaves ``b`` out of the selection, reads a gap."""
    out = {}
    for i, (name, shape) in enumerate(_shapes(model).items()):
        n = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("scale"):
            out[name] = 1.0 + 0.1 * n
        elif name == "embed/embedding" or name.endswith("moe/bias"):
            out[name] = 0.02 * n
        elif name == "lm_head/kernel":
            out[name] = n / jnp.sqrt(jnp.float32(shape[1]))
        else:  # (fan_in, fan_out), or a bank (experts, fan_in, fan_out)
            out[name] = n / jnp.sqrt(jnp.float32(shape[-2]))
    return out


def rows(batch) -> int:
    return int(batch[0].shape[0])


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    t, dh = x.shape[1], x.shape[3]
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(mm, q, k, v, window: int):
    """Causal (``window`` 0) or sliding-window softmax attention with
    grouped K/V heads.  Departure from the published description, for
    memory only: computed one K/V head and one block of query rows at a
    time (``lax.map`` over a checkpointed body); the numbers are those
    of the whole product."""
    b, t, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qb = _Q_BLOCK if t % _Q_BLOCK == 0 else t
    nb = t // qb
    # (hkv * nb, b, qb, g, dh): one K/V head's query heads, one block of rows
    qs = q.reshape(b, nb, qb, hkv, g, dh).transpose(3, 1, 0, 2, 4, 5)
    qs = qs.reshape(hkv * nb, b, qb, g, dh)
    kt, vt = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)  # (hkv, b, t, dh)
    kpos = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        qi, j, i = args
        kj, vj = kt[j], vt[j]
        s = mm("bqgd,bkd->bgqk", qi, kj) / jnp.sqrt(jnp.float32(dh))
        qpos = i * qb + jnp.arange(qb)
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
        return mm("bgqk,bkd->bqgd", p, vj)

    idx = jnp.arange(hkv * nb)
    o = jax.lax.map(one, (qs, idx // nb, idx % nb))  # (hkv * nb, b, qb, g, dh)
    o = o.reshape(hkv, nb, b, qb, g, dh).transpose(2, 1, 3, 0, 4, 5)
    return o.reshape(b, t, h * dh)


def _swiglu(mm, x, wg, wi, wo):
    return mm("nf,fd->nd", jax.nn.silu(mm("nd,df->nf", x, wg)) * mm("nd,df->nf", x, wi), wo)


def _moe(mm, m, p, model):
    """The expert layer over flat tokens ``m`` (N, d), this program's
    share: the shared expert once, and for every held expert, computed
    on every token, its output weighted by ``w_e`` where the token chose
    it and by 0 where it did not.  Choices of experts held elsewhere add
    nothing (their owners' part is not this chip's)."""
    e, k, held = model["num_experts"], model["expert_top_k"], model["experts_held"]
    lo = model.get("expert_share_index", 0) * held
    # The router's product stays in float32 at the highest precision in
    # every precision this reference is run in (the control's too): the
    # configuration states a float32 router, and the control lowers only
    # what the stated precision computes in bfloat16.
    s = jax.nn.sigmoid(jnp.dot(m, p["moe/router/kernel"],
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["moe/bias"]), k)
    chosen = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1)  # (N, E) 0/1
    w = model["route_scale"] * s * chosen / (
        jnp.sum(s * chosen, axis=-1, keepdims=True) + 1e-20)
    out = _swiglu(mm, m, p["moe/shared/wg/kernel"], p["moe/shared/wi/kernel"],
                  p["moe/shared/wo/kernel"])
    # One held expert at a time, every token through it, each its own
    # checkpoint: the backward pass holds one expert's hidden rows, not
    # all of them.  A loop over the bank's leading axis (``lax.scan``: one
    # compiled body, not one per expert).
    expert = jax.checkpoint(lambda m, wg, wi, wo: _swiglu(mm, m, wg, wi, wo))

    def add(out, bank):
        wg, wi, wo, w_e = bank
        return out + w_e[:, None] * expert(m, wg, wi, wo), None

    out, _ = jax.lax.scan(
        add, out, (p["moe/wg"], p["moe/wi"], p["moe/wo"], w[:, lo:lo + held].T))
    return out


def _hidden(params, inputs, model, precision):
    """``(mm, x)``: the precision's product and the final-normed hidden
    states (B * T, d) of ``inputs``."""
    cast, prec, round_back = precision
    h_n, hkv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    eps, theta = model["norm_eps"], model["rope_theta"]

    def mm(eq, a, b):
        return round_back(jnp.einsum(eq, cast(a), cast(b), precision=prec,
                                     preferred_element_type=jnp.float32))

    x = jnp.take(params["embed/embedding"], inputs, axis=0)
    x = x * jnp.sqrt(jnp.float32(model["d_model"]))
    b, t, d = x.shape

    def layer(i):
        """Layer ``i``'s two residual branches, each one ``jax.checkpoint``
        (a layer's backward pass then holds one branch's intermediates)."""
        sliding = model["layer_types"][i] == "sliding_attention"

        @jax.checkpoint
        def attend(x, p):
            a = _rms(x, p["norm_attn/scale"], eps)
            q = mm("btd,de->bte", a, p["attn/q/kernel"]).reshape(b, t, h_n, dh)
            k = mm("btd,de->bte", a, p["attn/k/kernel"]).reshape(b, t, hkv, dh)
            v = mm("btd,de->bte", a, p["attn/v/kernel"]).reshape(b, t, hkv, dh)
            q = _rms(q, p["attn/q_norm/scale"], eps)
            k = _rms(k, p["attn/k_norm/scale"], eps)
            if sliding:
                q, k = _rope(q, theta), _rope(k, theta)
            o = _attention(mm, q, k, v, model["sliding_window"] if sliding else 0)
            o = o * jax.nn.sigmoid(mm("btd,de->bte", a, p["attn/gate/kernel"]))
            return x + _rms(mm("bte,ed->btd", o, p["attn/out/kernel"]),
                            p["norm_post_attn/scale"], eps)

        @jax.checkpoint
        def feed(x, p):
            m = _rms(x, p["norm_mlp/scale"], eps).reshape(b * t, d)
            if i < model["num_dense_layers"]:
                y = _swiglu(mm, m, p["mlp/wg/kernel"], p["mlp/wi/kernel"],
                            p["mlp/wo/kernel"])
            else:
                y = _moe(mm, m, p, model)
            return x + _rms(y.reshape(b, t, d), p["norm_post_mlp/scale"], eps)

        return lambda x, p: feed(attend(x, p), p)

    for i in range(model["n_layers"]):
        prefix = f"block{i}/"
        p = {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}
        x = layer(i)(x, p)
    return mm, _rms(x, params["norm_f/scale"], eps).reshape(b * t, d)


def forward_logits(params, inputs, model, precision):
    """(B, T, V) logits, whole (the tests' sizes): what the loss below
    takes block by block."""
    mm, x = _hidden(params, inputs, model, precision)
    return mm("nd,vd->nv", x, params["lm_head/kernel"]).reshape(*inputs.shape, -1)


def _forward_loss(params, inputs, targets, model, precision):
    mm, x = _hidden(params, inputs, model, precision)
    n, d = x.shape
    # Departure, for memory only: the head and the loss go through in
    # blocks of rows, each its own checkpoint (the logits of 8192 tokens
    # over 25024 ids are 820 MB in float32, and their gradient as much).
    rb = _LOSS_BLOCK if n % _LOSS_BLOCK == 0 else n

    @jax.checkpoint
    def block_loss(args):
        xb, tb = args
        logits = mm("nd,vd->nv", xb, params["lm_head/kernel"])
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - picked)

    return jnp.sum(jax.lax.map(
        block_loss, (x.reshape(n // rb, rb, d), targets.reshape(n // rb, rb))))


def make_grad_fn(model: dict, precision, row_block: int = 0):
    """``(params, (inputs, targets)) -> (mean loss, grads)``.  ``row_block``
    is taken and not used: a batch of 2 has no blocks of rows to go
    through; what bounds memory here is the blocking of the attention
    and of the loss."""
    del row_block

    def grad_fn(params, batch):
        inputs, targets = (jnp.asarray(a, jnp.int32) for a in batch)
        n, t = inputs.shape
        loss, grads = jax.value_and_grad(
            lambda p: _forward_loss(p, inputs, targets, model, precision)
        )(params)
        scale = 1.0 / (n * t)
        return loss * scale, jax.tree.map(lambda g: g * scale, grads)

    return grad_fn
