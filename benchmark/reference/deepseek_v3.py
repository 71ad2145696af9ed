"""Plain reference of the deepseek_v3 stack (Kanana-2-30B-A3B's family) at
any widths.

The equations, with the configuration's keys in brackets; they follow the
published ``deepseek_v3`` modelling code, and what ``config.json`` does
not say is listed under ``assumed`` in the configuration file.  No biases
anywhere [``attention_bias`` false]; RMSNorm in float32 with a learned
scale, eps [``rms_norm_eps``].

    h0 = E[tokens]                                          (no scaling)
    block:  a = RMSNorm_in(x)
      latent attention, every layer [``q_lora_rank`` null: no query latent]:
            q = heads(a Wq)          each head [q_nope, q_pe]   [qk_nope_head_dim, qk_rope_head_dim]
            [c, k_pe] = a Wkva       c [kv_lora_rank] wide, k_pe one head every head shares
            [k_nope, v] = heads(RMSNorm_kv(c) Wkvb)             [v_head_dim]
            q_pe, k_pe rotated pair by pair: dims (2i, 2i+1) turn by position
            * theta^(-2i / qk_rope_head_dim)          [rope_theta, rope_interleave]
            Q = [q_nope, q_pe], K = [k_nope, k_pe], V = v
            x = x + (softmax(Q K^T / sqrt(qk_nope + qk_rope) + causal mask) V) Wo
            m = RMSNorm_mlp(x)
    F, the leading dense layers [first_k_dense_replace]:
            x = x + (silu(m Wgate) * (m Wup)) Wdown         [intermediate_size]
    F, expert layers:
            s = sigmoid(m Wr) in float32 over all the experts   [n_routed_experts, scoring_func]
            S = top-k of (s + b), b not trained             [num_experts_per_tok,
                                                            e_score_correction_bias; n_group = topk_group = 1]
            w_e = routed_scaling_factor * s_e / (sum_{e' in S} s_e' + 1e-20)   [norm_topk_prob]
            x = x + Shared(m) + sum_{e in S, e held here} w_e (silu(m Wg_e) * (m Wu_e)) Wd_e
            Shared: one SwiGLU moe_intermediate_size * n_shared_experts wide
    logits = RMSNorm_f(h_L) W_head (untied); mean next-token cross-entropy in f32.

No router auxiliary loss, no multi-token prediction.  No kernels, no sort,
no cache: the rotation as an explicit loop over pairs, K built by
repeating ``k_pe`` for every head, a loop over the held experts with
masks.  Departures, for memory only (the numbers are those of the whole
products): attention one head and one block of query rows at a time (one
head's 4096 x 4096 float32 scores are 67 MB); the head and the loss in
blocks of rows; each residual branch of a layer one ``jax.checkpoint``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["init_params", "make_grad_fn", "rows", "leaf_names", "forward_logits",
           "moe_layer", "latent_attention", "rotate_pairs"]

# query rows an attention block: (2, 512, 4096) float32 scores are 17 MB
_Q_BLOCK = 512
# token rows a block of the head and the loss
_LOSS_BLOCK = 2048
# the normalisation's epsilon of the published deepseek_v3 code
_ROUTE_EPS = 1e-20


def _shapes(model: dict) -> dict:
    d, h, v = model["d_model"], model["n_heads"], model["vocab_size"]
    nope, rot, dv, rank = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                           model["v_head_dim"], model["kv_lora_rank"])
    f, fe, fs = model["d_ff"], model["moe_d_ff"], model["moe_d_ff"] * model["num_shared_experts"]
    e, held = model["num_experts"], model["experts_held"]
    shapes = {"embed/embedding": (v, d)}
    for i in range(model["n_layers"]):
        per = {
            "norm_attn/scale": (d,), "mla/q/kernel": (d, h * (nope + rot)),
            "mla/kv_a/kernel": (d, rank + rot), "mla/kv_norm/scale": (rank,),
            "mla/kv_b/kernel": (rank, h * (nope + dv)), "mla/out/kernel": (h * dv, d),
            "norm_mlp/scale": (d,),
        }
        if i < model["num_dense_layers"]:
            per.update({"mlp/wg/kernel": (d, f), "mlp/wi/kernel": (d, f),
                        "mlp/wo/kernel": (f, d)})
        else:
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
    1/sqrt(fan_in), the head's over d_model, norm scales 1 + 0.1 n, the
    embedding 0.02 n, and the selection bias ``b`` 0.02 n: not zero, so
    that a program that selects by the unbiased score, or weights by
    ``s + b``, reads a gap."""
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


def rotate_pairs(x, theta):
    """``x`` (B, T, H, R) with dims ``(2i, 2i + 1)`` turned by position
    ``p`` through the angle ``p * theta^(-2i / R)``, one pair at a time."""
    t, r = x.shape[1], x.shape[-1]
    pos = jnp.arange(t, dtype=jnp.float32)[None, :, None]
    out = []
    for i in range(r // 2):
        ang = pos * jnp.float32(theta) ** (-jnp.float32(2 * i) / r)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        out += [a * cos - b * sin, a * sin + b * cos]
    return jnp.stack(out, axis=-1)


def _attention(mm, q, k, v):
    """Causal softmax attention, one head and one block of query rows at
    a time (``lax.map`` over a checkpointed body); Q and K are wider than
    V, and the scale is Q's width."""
    b, t, h, dq = q.shape
    dv = v.shape[-1]
    qb = _Q_BLOCK if t % _Q_BLOCK == 0 else t
    nb = t // qb
    qs = q.reshape(b, nb, qb, h, dq).transpose(3, 1, 0, 2, 4).reshape(h * nb, b, qb, dq)
    kt, vt = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)  # (h, b, t, .)
    kpos = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        qi, j, i = args
        s = mm("bqd,bkd->bqk", qi, kt[j]) / jnp.sqrt(jnp.float32(dq))
        mask = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return mm("bqk,bkd->bqd", p, vt[j])

    idx = jnp.arange(h * nb)
    o = jax.lax.map(one, (qs, idx // nb, idx % nb))  # (h * nb, b, qb, dv)
    return o.reshape(h, nb, b, qb, dv).transpose(2, 1, 3, 0, 4).reshape(b, t, h * dv)


def latent_attention(mm, a, p, model):
    """The latent attention of normed inputs ``a`` (B, T, d), as written
    above."""
    b, t, _ = a.shape
    h, nope, rot = model["n_heads"], model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, dv = model["kv_lora_rank"], model["v_head_dim"]
    q = mm("btd,de->bte", a, p["mla/q/kernel"]).reshape(b, t, h, nope + rot)
    ckv = mm("btd,de->bte", a, p["mla/kv_a/kernel"])
    c = _rms(ckv[..., :rank], p["mla/kv_norm/scale"], model["norm_eps"])
    kv = mm("btr,re->bte", c, p["mla/kv_b/kernel"]).reshape(b, t, h, nope + dv)
    theta = model["rope_theta"]
    q_pe = rotate_pairs(q[..., nope:], theta)
    k_pe = rotate_pairs(ckv[..., None, rank:], theta)
    qh = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    kh = jnp.concatenate([kv[..., :nope], jnp.repeat(k_pe, h, axis=2)], axis=-1)
    return mm("bte,ed->btd", _attention(mm, qh, kh, kv[..., nope:]), p["mla/out/kernel"])


def _swiglu(mm, x, wg, wi, wo):
    return mm("nf,fd->nd", jax.nn.silu(mm("nd,df->nf", x, wg)) * mm("nd,df->nf", x, wi), wo)


def moe_layer(mm, m, p, model):
    """The expert layer over flat tokens ``m`` (N, d), this program's
    share: the shared experts once, and for every held
    expert, computed on every token, its output weighted by ``w_e`` where
    the token chose it and by 0 where it did not.  Choices of experts
    held elsewhere add nothing (their owners' part is not this chip's)."""
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
    out = _swiglu(mm, m, p["moe/shared/wg/kernel"], p["moe/shared/wi/kernel"],
                  p["moe/shared/wo/kernel"])
    # One held expert at a time, every token through it, each its own
    # checkpoint: the backward pass holds one expert's hidden rows, not
    # all of them (``lax.scan``: one compiled body, not one per expert).
    expert = jax.checkpoint(lambda m, wg, wi, wo: _swiglu(mm, m, wg, wi, wo))

    def add(out, bank):
        wg, wi, wo, w_e = bank
        return out + w_e[:, None] * expert(m, wg, wi, wo), None

    out, _ = jax.lax.scan(
        add, out, (p["moe/wg"], p["moe/wi"], p["moe/wo"], w[:, lo:lo + held].T))
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
    eps = model["norm_eps"]
    x = jnp.take(params["embed/embedding"], inputs, axis=0)
    b, t, d = x.shape

    def layer(i):
        """Layer ``i``'s two residual branches, each one ``jax.checkpoint``
        (a layer's backward pass then holds one branch's intermediates)."""

        @jax.checkpoint
        def attend(x, p):
            return x + latent_attention(mm, _rms(x, p["norm_attn/scale"], eps), p, model)

        @jax.checkpoint
        def feed(x, p):
            m = _rms(x, p["norm_mlp/scale"], eps).reshape(b * t, d)
            if i < model["num_dense_layers"]:
                y = _swiglu(mm, m, p["mlp/wg/kernel"], p["mlp/wi/kernel"], p["mlp/wo/kernel"])
            else:
                y = moe_layer(mm, m, p, model)
            return x + y.reshape(b, t, d)

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
    # the head and the loss in blocks of rows, each its own checkpoint (the
    # logits of 8192 tokens over 16032 ids are 525 MB in float32, and their
    # gradient as much)
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
