"""Plain reference of the lfm2_moe stack (LFM2-8B-A1B's family) at any
widths.

The equations, with the configuration's keys in brackets; they follow the
published ``lfm2_moe`` modelling code, and what ``config.json`` does not
say is listed under ``assumed`` in the configuration file.  No biases
anywhere [``conv_bias`` false]; RMSNorm in float32 with a learned scale,
eps [``norm_eps``].

    h0 = E[tokens]                                          (no scaling)
    block:  a = RMSNorm_in(x)
      conv layer [layer_types "conv"]:
            [B, C, u] = split3(a W_in)                      (W_in d -> 3d, that order)
            v_t = sum_{j=0..L-1} w_j * (B * u)_{t-L+1+j}     causal, depthwise, L taps
                                                            [conv_L_cache]
            x = x + (C * v) W_out
      attention layer [layer_types "full_attention"]:
            q = RMSNorm_q(heads(a Wq)), k = RMSNorm_k(heads(a Wk))   over each head's head_dim
            v = heads(a Wv); half-split rotary on q and k, theta [rope_theta]
            x = x + (softmax(q k^T / sqrt(head_dim) + causal mask) v) Wo
            (grouped K/V heads [num_key_value_heads])
            m = RMSNorm_mlp(x)
    F, the leading dense layers [num_dense_layers]:
            x = x + (silu(m Wgate) * (m Wup)) Wdown         [intermediate_size]
    F, expert layers:
            s = sigmoid(m Wr) in float32 over all the experts   [num_experts]
            S = top-k of (s + b), b not trained             [num_experts_per_tok, use_expert_bias]
            w_e = routed_scaling_factor * s_e / (sum_{e' in S} s_e' + 1e-6)   [norm_topk_prob]
            x = x + sum_{e in S, e held here} w_e (silu(m Wg_e) * (m Wu_e)) Wd_e
    logits = RMSNorm_f(h_L) E^T (the head tied to the embedding); mean
    next-token cross-entropy in f32.

No router auxiliary loss, no shared expert.  No kernels, no sort, no cache:
the convolution as its taps, a loop over the held experts with masks.
Departures, for memory only (the numbers are those of the whole
products): attention in blocks over K/V heads and query rows (one head's
4096 x 4096 float32 scores are 67 MB); the head and the loss in blocks of
rows; each residual branch of a layer one ``jax.checkpoint``.  The
convolution's taps and its two products are elementwise float32 in every
precision this reference is run in, as the program computes them: a
control lowers what the stated precision computes in bfloat16 (the matrix
products).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["init_params", "make_grad_fn", "rows", "leaf_names", "forward_logits",
           "moe_layer", "short_conv"]

# query rows an attention block: (2, 4, 512, 4096) float32 scores are 67 MB
_Q_BLOCK = 512
# token rows a block of the head and the loss
_LOSS_BLOCK = 2048
# the normalisation's epsilon of the published lfm2_moe code
_ROUTE_EPS = 1e-6


def _shapes(model: dict) -> dict:
    d, h, hkv, dh = model["d_model"], model["n_heads"], model["n_kv_heads"], model["head_dim"]
    v, f, fe = model["vocab_size"], model["d_ff"], model["moe_d_ff"]
    e, held, taps = model["num_experts"], model["experts_held"], model["short_conv"]
    shapes = {"embed/embedding": (v, d)}
    for i, kind in enumerate(model["layer_types"]):
        per = {"norm_attn/scale": (d,)}
        if kind == "conv":
            per.update({"conv/in_proj/kernel": (d, 3 * d), "conv/conv/kernel": (taps, d),
                        "conv/out_proj/kernel": (d, d)})
        elif kind == "full_attention":
            per.update({
                "attn/q/kernel": (d, h * dh), "attn/k/kernel": (d, hkv * dh),
                "attn/v/kernel": (d, hkv * dh), "attn/q_norm/scale": (dh,),
                "attn/k_norm/scale": (dh,), "attn/out/kernel": (h * dh, d),
            })
        else:
            raise ValueError(f"layer {i}: unknown kind {kind!r}")
        per["norm_mlp/scale"] = (d,)
        if i < model["num_dense_layers"]:
            per.update({"mlp/wg/kernel": (d, f), "mlp/wi/kernel": (d, f),
                        "mlp/wo/kernel": (f, d)})
        else:
            per.update({"moe/router/kernel": (d, e), "moe/bias": (e,),
                        "moe/wg": (held, d, fe), "moe/wi": (held, d, fe),
                        "moe/wo": (held, fe, d)})
        for name, shape in per.items():
            shapes[f"block{i}/{name}"] = shape
    shapes["norm_f/scale"] = (d,)
    return shapes


def leaf_names(model: dict) -> list[str]:
    return list(_shapes(model))


def init_params(key, model: dict) -> dict:
    """The cell's weights from the seed's key: matrices normal with std
    1/sqrt(fan_in) (the convolution's fan-in is its taps), norm scales
    1 + 0.1 n, the embedding 0.02 n (it is the head too: at std 1 the tied
    logits would have a std of sqrt(d)), and the selection bias ``b``
    0.02 n: not zero, so that a program that selects by the unbiased
    score, or weights by ``s + b``, reads a gap."""
    out = {}
    for i, (name, shape) in enumerate(_shapes(model).items()):
        n = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("scale"):
            out[name] = 1.0 + 0.1 * n
        elif name == "embed/embedding" or name.endswith("moe/bias"):
            out[name] = 0.02 * n
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


def _attention(mm, q, k, v):
    """Causal softmax attention with grouped K/V heads, one K/V head and
    one block of query rows at a time (``lax.map`` over a checkpointed
    body); the numbers are those of the whole product."""
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
        s = mm("bqgd,bkd->bgqk", qi, kt[j]) / jnp.sqrt(jnp.float32(dh))
        mask = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
        return mm("bgqk,bkd->bqgd", p, vt[j])

    idx = jnp.arange(hkv * nb)
    o = jax.lax.map(one, (qs, idx // nb, idx % nb))  # (hkv * nb, b, qb, g, dh)
    o = o.reshape(hkv, nb, b, qb, g, dh).transpose(2, 1, 3, 0, 4, 5)
    return o.reshape(b, t, h * dh)


def short_conv(mm, a, p):
    """The gated short convolution of normed inputs ``a`` (B, T, d), as
    written above: the split, the causal taps one by one, the gate."""
    d = a.shape[-1]
    bcx = mm("btd,de->bte", a, p["conv/in_proj/kernel"])
    bg, cg, u = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    w = p["conv/conv/kernel"]
    taps, t = w.shape[0], a.shape[1]
    bu = jnp.pad(bg * u, ((0, 0), (taps - 1, 0), (0, 0)))
    v = sum(w[j] * bu[:, j:j + t] for j in range(taps))
    return mm("btd,de->bte", cg * v, p["conv/out_proj/kernel"])


def _swiglu(mm, x, wg, wi, wo):
    return mm("nf,fd->nd", jax.nn.silu(mm("nd,df->nf", x, wg)) * mm("nd,df->nf", x, wi), wo)


def moe_layer(mm, m, p, model):
    """The expert layer over flat tokens ``m`` (N, d), this program's
    share: for every held expert, computed on every token, its output
    weighted by ``w_e`` where the token chose it and by 0 where it did
    not.  Choices of experts held elsewhere add nothing (their owners'
    part is not this chip's)."""
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
        jnp.sum(s * chosen, axis=-1, keepdims=True) + _ROUTE_EPS)
    # One held expert at a time, every token through it, each its own
    # checkpoint: the backward pass holds one expert's hidden rows, not
    # all of them (``lax.scan``: one compiled body, not one per expert).
    expert = jax.checkpoint(lambda m, wg, wi, wo: _swiglu(mm, m, wg, wi, wo))

    def add(out, bank):
        wg, wi, wo, w_e = bank
        return out + w_e[:, None] * expert(m, wg, wi, wo), None

    out, _ = jax.lax.scan(
        add, jnp.zeros_like(m),
        (p["moe/wg"], p["moe/wi"], p["moe/wo"], w[:, lo:lo + held].T))
    return out


def _mm(precision):
    cast, prec, round_back = precision

    def mm(eq, a, b):
        return round_back(jnp.einsum(eq, cast(a), cast(b), precision=prec,
                                     preferred_element_type=jnp.float32))

    return mm


def _hidden(params, inputs, model, precision):
    """``(mm, x)``: the precision's product and the final-normed hidden
    states (B * T, d) of ``inputs``."""
    mm = _mm(precision)
    h_n, hkv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    eps, theta = model["norm_eps"], model["rope_theta"]
    x = jnp.take(params["embed/embedding"], inputs, axis=0)
    b, t, d = x.shape

    def layer(i):
        """Layer ``i``'s two residual branches, each one ``jax.checkpoint``
        (a layer's backward pass then holds one branch's intermediates)."""
        conv = model["layer_types"][i] == "conv"

        @jax.checkpoint
        def mix(x, p):
            a = _rms(x, p["norm_attn/scale"], eps)
            if conv:
                return x + short_conv(mm, a, p)
            q = mm("btd,de->bte", a, p["attn/q/kernel"]).reshape(b, t, h_n, dh)
            k = mm("btd,de->bte", a, p["attn/k/kernel"]).reshape(b, t, hkv, dh)
            v = mm("btd,de->bte", a, p["attn/v/kernel"]).reshape(b, t, hkv, dh)
            q = _rope(_rms(q, p["attn/q_norm/scale"], eps), theta)
            k = _rope(_rms(k, p["attn/k_norm/scale"], eps), theta)
            return x + mm("bte,ed->btd", _attention(mm, q, k, v), p["attn/out/kernel"])

        @jax.checkpoint
        def feed(x, p):
            m = _rms(x, p["norm_mlp/scale"], eps).reshape(b * t, d)
            if i < model["num_dense_layers"]:
                y = _swiglu(mm, m, p["mlp/wg/kernel"], p["mlp/wi/kernel"], p["mlp/wo/kernel"])
            else:
                y = moe_layer(mm, m, p, model)
            return x + y.reshape(b, t, d)

        return lambda x, p: feed(mix(x, p), p)

    for i in range(model["n_layers"]):
        prefix = f"block{i}/"
        p = {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}
        x = layer(i)(x, p)
    return mm, _rms(x, params["norm_f/scale"], eps).reshape(b * t, d)


def forward_logits(params, inputs, model, precision):
    """(B, T, V) logits, whole (the tests' sizes): what the loss below
    takes block by block."""
    mm, x = _hidden(params, inputs, model, precision)
    return mm("nd,vd->nv", x, params["embed/embedding"]).reshape(*inputs.shape, -1)


def _forward_loss(params, inputs, targets, model, precision):
    mm, x = _hidden(params, inputs, model, precision)
    n, d = x.shape
    # the head and the loss in blocks of rows, each its own checkpoint (the
    # logits of 8192 tokens over 16384 ids are 537 MB in float32, and their
    # gradient as much)
    rb = _LOSS_BLOCK if n % _LOSS_BLOCK == 0 else n

    @jax.checkpoint
    def block_loss(args):
        xb, tb = args
        logits = mm("nd,vd->nv", xb, params["embed/embedding"])
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
