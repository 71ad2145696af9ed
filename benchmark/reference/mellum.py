"""Plain reference of the mellum block (Mellum2-12B-A2.5B's family) at any
widths.

The equations, with the configuration's keys in brackets; what the keys
do not say is the Qwen3-MoE lineage's convention, from which ``mellum``'s
keys descend, and is listed under ``assumed`` in the configuration file.
No biases anywhere [``attention_bias`` false]; RMSNorm in float32, eps
[``rms_norm_eps``].  Every layer's MLP is sparse [``mlp_layer_types``]:
no dense layer, no shared expert.

    h0 = E[tokens]                                          (no scaling)
    block:  a = RMSNorm_in(x)
            q = RMSNorm_q(heads(a Wq)), k = RMSNorm_k(heads(a Wk))   over each head's head_dim
            v = heads(a Wv)
            half-split rotary on q and k in every layer, by the layer's
            kind [rope_parameters]:
              sliding_attention: inv_freq_i = theta^(-2i/head_dim)
              full_attention (YaRN): ext_i = theta^(-2i/head_dim), int_i = ext_i / factor,
                c(n) = head_dim ln(original / (2 pi n)) / (2 ln theta),
                low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
                r_i = clip((i - low) / (high - low), 0, 1),
                inv_freq_i = int_i r_i + ext_i (1 - r_i);
                cos and sin are multiplied by attention_factor
            a sliding layer sees keys i - window < j <= i [sliding_window],
            a full layer j <= i                             [layer_types]
            x = x + (softmax(q k^T / sqrt(head_dim) + mask) v) Wo
            m = RMSNorm_post(x)
            p = softmax(m Wr) in float32 over all the experts  [num_experts]
            S = top-k of p                                  [num_experts_per_tok]
            w_e = p_e / sum_{e' in S} p_e'                  [norm_topk_prob]
            x = x + sum_{e in S, e held here} w_e (silu(m Wg_e) * (m Wu_e)) Wd_e
    logits = RMSNorm_f(h_L) W_head (untied); mean next-token cross-entropy in f32.

No router auxiliary loss, no selection bias, no multi-token-prediction
head.  No kernels, no sort, no cache: a loop over the held experts with
masks, and attention in blocks over K/V heads and query rows (one head's
4096 x 4096 float32 scores are 67 MB; 32 heads with their gradients do
not fit beside the published-width parameters, their gradients and Adam's
moments otherwise).  Each residual branch of a layer is one
``jax.checkpoint``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["init_params", "make_grad_fn", "rows", "leaf_names", "forward_logits",
           "inv_freq", "moe_layer"]

# query rows an attention block: (2, 8, 512, 4096) float32 scores are 134 MB
_Q_BLOCK = 512
# token rows a block of the head and the loss
_LOSS_BLOCK = 2048


def _shapes(model: dict) -> dict:
    d, h, hkv, dh = model["d_model"], model["n_heads"], model["n_kv_heads"], model["head_dim"]
    v, fe = model["vocab_size"], model["moe_d_ff"]
    e, held = model["num_experts"], model["experts_held"]
    shapes = {"embed/embedding": (v, d)}
    for i in range(model["n_layers"]):
        per = {
            "norm_attn/scale": (d,), "attn/q/kernel": (d, h * dh),
            "attn/k/kernel": (d, hkv * dh), "attn/v/kernel": (d, hkv * dh),
            "attn/q_norm/scale": (dh,), "attn/k_norm/scale": (dh,),
            "attn/out/kernel": (h * dh, d), "norm_mlp/scale": (d,),
            "moe/router/kernel": (d, e),
            "moe/wg": (held, d, fe), "moe/wi": (held, d, fe), "moe/wo": (held, fe, d),
        }
        for name, shape in per.items():
            shapes[f"block{i}/{name}"] = shape
    shapes["norm_f/scale"] = (d,)
    shapes["lm_head/kernel"] = (v, d)
    return shapes


def leaf_names(model: dict) -> list[str]:
    return list(_shapes(model))


def init_params(key, model: dict) -> dict:
    """The cell's weights from the seed's key: matrices normal with std
    1/sqrt(fan_in), norm scales 1 + 0.1 n, the embedding normal with std 1:
    the residual stream then starts at the scale the branches add to it,
    and a token's own row, not the attention's average over its context,
    decides its routing.  (With the other families' 0.02 and no embedding
    multiplier the attention output, about 0.05 an element, drowns the
    row: every token of a batch then sends the router nearly the same
    vector, cosine 0.7-0.8, and chooses the same 8 experts; measured on
    the chip, PERF.md s6 PR 33.)"""
    out = {}
    for i, (name, shape) in enumerate(_shapes(model).items()):
        n = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("scale"):
            out[name] = 1.0 + 0.1 * n
        elif name == "embed/embedding":
            out[name] = n
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


def inv_freq(rope: dict, head_dim: int):
    """``(frequencies (head_dim / 2,), the factor on cos and sin)`` of one
    kind of layer's ``rope`` entry: plain where it has no ``factor``."""
    half = head_dim // 2
    i = jnp.arange(half, dtype=jnp.float32)
    ext = jnp.float32(rope["theta"]) ** (-i / half)
    if "factor" not in rope:
        return ext, 1.0

    def c(n):
        return (head_dim * math.log(rope["original"] / (2 * math.pi * n))
                / (2 * math.log(rope["theta"])))

    low, high = math.floor(c(rope["beta_fast"])), math.ceil(c(rope["beta_slow"]))
    r = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (ext / rope["factor"]) * r + ext * (1.0 - r), rope["attention_factor"]


def _rotate(x, rope: dict):
    t, dh = x.shape[1], x.shape[3]
    half = dh // 2
    freqs, factor = inv_freq(rope, dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos = (factor * jnp.cos(ang))[None, :, None, :]
    sin = (factor * jnp.sin(ang))[None, :, None, :]
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
    s = jax.nn.softmax(jnp.dot(m, p["moe/router/kernel"],
                               precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, idx = jax.lax.top_k(s, k)
    chosen = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1)  # (N, E) 0/1
    w = s * chosen / jnp.sum(s * chosen, axis=-1, keepdims=True)
    # One held expert at a time, every token through it, each its own
    # checkpoint: the backward pass holds one expert's hidden rows, not
    # all of them.  A loop over the bank's leading axis (``lax.scan``: one
    # compiled body, not one per expert).
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
    eps = model["norm_eps"]
    x = jnp.take(params["embed/embedding"], inputs, axis=0)
    b, t, d = x.shape

    def layer(i):
        """Layer ``i``'s two residual branches, each one ``jax.checkpoint``
        (a layer's backward pass then holds one branch's intermediates)."""
        kind = model["layer_types"][i]
        rope = model["rope"][kind]
        window = model["sliding_window"] if kind == "sliding_attention" else 0

        @jax.checkpoint
        def attend(x, p):
            a = _rms(x, p["norm_attn/scale"], eps)
            q = mm("btd,de->bte", a, p["attn/q/kernel"]).reshape(b, t, h_n, dh)
            k = mm("btd,de->bte", a, p["attn/k/kernel"]).reshape(b, t, hkv, dh)
            v = mm("btd,de->bte", a, p["attn/v/kernel"]).reshape(b, t, hkv, dh)
            q = _rotate(_rms(q, p["attn/q_norm/scale"], eps), rope)
            k = _rotate(_rms(k, p["attn/k_norm/scale"], eps), rope)
            o = _attention(mm, q, k, v, window)
            return x + mm("bte,ed->btd", o, p["attn/out/kernel"])

        @jax.checkpoint
        def feed(x, p):
            m = _rms(x, p["norm_mlp/scale"], eps).reshape(b * t, d)
            return x + moe_layer(mm, m, p, model).reshape(b, t, d)

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
    # over 12288 ids are 403 MB in float32, and their gradient as much).
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
