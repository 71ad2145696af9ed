"""Plain reference of the SambaY decoder-hybrid-decoder stack
(Phi-4-mini-flash-reasoning's family) at any widths.

The equations, with the configuration's keys in brackets.  They follow
"Decoder-Hybrid-Decoder Architecture for Efficient Reasoning with Long
Generation" (arXiv:2507.06607) and that model's public modelling code
(``modeling_phi4flash.py``); what ``config.json`` does not say is listed
under ``assumed`` in the configuration file (no copy of the modelling
file is on this machine, so nothing below was checked against it line by
line).

Residual stream ``x`` (T x d).  Every layer

    h = x + Mixer(LN1(x));   y = h + MLP(LN2(h))

``LN`` is LayerNorm with scale and bias, eps [``layer_norm_eps``], in
float32.  ``MLP(u) = W_down(silu(g) * v)``, ``g = W_gate u``,
``v = W_up u`` (the published fused ``gate_up`` matrix, split), no bias
[``mlp_bias``].  Dropouts are 0.  No rotary, no learned positions.  The
embedding is not scaled; after the last layer a final LayerNorm, then
``logits = x E^T`` with the embedding's own rows
[``tie_word_embeddings``], mean next-token cross-entropy in float32.

Which mixer a layer has is the file's ``layer_types`` (published layer
``i`` of 32, ``mb_per_layer`` 2: even ``i`` a Mamba-kind mixer, odd ``i``
an attention-kind one; ``i < 16`` Mamba and sliding attention, ``i = 16``
the Mamba whose scan output is kept, ``i = 17`` the full attention whose
K and V are kept, ``i >= 18`` GMU and cross-attention).  Here a GMU reads
the scan output of the nearest Mamba layer before it and a cross layer
the K/V of the nearest full-attention layer before it: the same thing
for the published layout and for any contiguous cut of it.

Mamba (d_in = expand * d, state N, convolution K, rank R):
    [xs, z] = [W_inx u, W_inz u]              (the fused in-projection, split)
    xc = silu(conv_K(xs) + b_conv)            causal, depthwise
    [r, B, C] = split(W_x xc)                 widths R, N, N
    dt = softplus(W_dt r + b_dt);  A = -exp(A_log)          (d_in x N)
    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * xc_t) B_t^T;  h_{-1} = 0
    s_t = h_t C_t + D * xc_t
    out = W_out(s * silu(z));   the layer hands m = s down the stack
GMU:  out = W_out(m * silu(W_in u))
Differential attention (sliding and full layers):
    q, k, v = W_q u + b_q, W_k u + b_k, W_v u + b_v   (the fused W_qkv, split)
    heads pair up (2j, 2j+1); query pair j attends K/V pair j // (pairs a K/V pair)
    A1 = softmax(mask(q1 k1^T / sqrt(head_dim))), A2 likewise of (q2, k2)
    V = [v1, v2];  o = (A1 - lam * A2) V
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,
    lam0 = 0.8 - 0.6 exp(-0.3 i), i the layer's published index [``layer_indices``]
    o <- RMSNorm_{2 head_dim}(o) * (1 - lam0);  out = W_o concat(o) + b_o
    mask: causal; a sliding layer's query t sees keys (t - window, t]
Cross-attention: q = W_q u + b_q is the layer's own; K and V are the kept
    ones, as their layer projected them from its own normed input; the same
    differential attention with the layer's own lq*, lk*, RMSNorm and W_o,
    causal over all positions.

Departures, for memory only (the numbers are those of the whole
products): one ``jax.checkpoint`` a layer; the scan over time in chunks
whose inside is recomputed going back; the MLP, the head and the loss in
blocks of rows; attention one K/V pair and one block of query rows at a
time.  The scan itself is elementwise float32 in every precision this
reference is run in: the configuration states a float32 scan, and a
control lowers only what the stated precision computes in bfloat16 (the
matrix products).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["init_params", "make_grad_fn", "rows", "leaf_names", "forward_logits",
           "lam0_of"]

_Q_BLOCK = 256       # query rows an attention block
_ROW_BLOCK = 1024    # token rows a block of the MLP, the head and the loss
_SCAN_CHUNK = 64     # time steps a checkpointed chunk of the scan

_SELF = ("sliding_attention", "full_attention")
_KINDS = ("mamba", "gmu", "cross_attention") + _SELF


def lam0_of(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _sizes(model: dict):
    d = model["d_model"]
    return (d, model["ssm_expand"] * d, model["ssm_state"], model["ssm_conv"],
            model["ssm_dt_rank"])


def _shapes(model: dict) -> dict:
    d, d_in, n, kc, r = _sizes(model)
    h, hkv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    f, v = model["d_ff"], model["vocab_size"]
    shapes = {"embed/embedding": (v, d)}
    for i, kind in enumerate(model["layer_types"]):
        if kind not in _KINDS:
            raise ValueError(f"layer {i}: unknown kind {kind!r}")
        per = {"norm_attn/scale": (d,), "norm_attn/bias": (d,)}
        if kind == "mamba":
            per.update({
                "ssm/in_x/kernel": (d, d_in), "ssm/in_z/kernel": (d, d_in),
                "ssm/conv/kernel": (kc, d_in), "ssm/conv/bias": (d_in,),
                "ssm/x_proj/kernel": (d_in, r + 2 * n),
                "ssm/dt_proj/kernel": (r, d_in), "ssm/dt_proj/bias": (d_in,),
                "ssm/A_log": (d_in, n), "ssm/D": (d_in,),
                "ssm/out_proj/kernel": (d_in, d),
            })
        elif kind == "gmu":
            per.update({"gmu/in_proj/kernel": (d, d_in), "gmu/out_proj/kernel": (d_in, d)})
        else:
            a = "xattn" if kind == "cross_attention" else "attn"
            per.update({f"{a}/q/kernel": (d, h * dh), f"{a}/q/bias": (h * dh,)})
            if kind in _SELF:
                per.update({f"{a}/k/kernel": (d, hkv * dh), f"{a}/k/bias": (hkv * dh,),
                            f"{a}/v/kernel": (d, hkv * dh), f"{a}/v/bias": (hkv * dh,)})
            per.update({f"{a}/out/kernel": (h * dh, d), f"{a}/out/bias": (d,),
                        f"{a}/lambda_q1": (dh,), f"{a}/lambda_k1": (dh,),
                        f"{a}/lambda_q2": (dh,), f"{a}/lambda_k2": (dh,),
                        f"{a}/subln/scale": (2 * dh,)})
        per.update({"norm_mlp/scale": (d,), "norm_mlp/bias": (d,),
                    "mlp/wg/kernel": (d, f), "mlp/wi/kernel": (d, f),
                    "mlp/wo/kernel": (f, d)})
        for name, shape in per.items():
            shapes[f"block{i}/{name}"] = shape
    shapes["norm_f/scale"] = (d,)
    shapes["norm_f/bias"] = (d,)
    return shapes


def leaf_names(model: dict) -> list[str]:
    return list(_shapes(model))


def init_params(key, model: dict) -> dict:
    """The cell's weights from the seed's key: matrices normal with std
    1/sqrt(fan_in), the embedding 0.02, norm scales 1 + 0.1 n, biases
    0.02 n, the lambda vectors 0.1 n (so that a program that drops
    ``lam`` reads a gap), ``A_log = log(1..N) + 0.1 n`` and
    ``D = 1 + 0.1 n`` (Mamba-1's initialisation, perturbed), the step's
    bias the inverse softplus of a step drawn log-uniformly from
    [1e-3, 1e-1] (Mamba-1's)."""
    out = {}
    for i, (name, shape) in enumerate(_shapes(model).items()):
        n = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        leaf = name.rsplit("/", 1)[-1]
        if leaf == "scale" or leaf == "D":
            out[name] = 1.0 + 0.1 * n
        elif name == "embed/embedding":
            out[name] = 0.02 * n
        elif leaf.startswith("lambda_"):
            out[name] = 0.1 * n
        elif leaf == "A_log":
            out[name] = jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)) + 0.1 * n
        elif name.endswith("dt_proj/bias"):
            u = jax.random.uniform(jax.random.fold_in(key, i), shape, jnp.float32)
            dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
            out[name] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
        elif leaf == "bias":
            out[name] = 0.02 * n
        else:  # (fan_in, fan_out); the convolution's (taps, channels)
            out[name] = n / jnp.sqrt(jnp.float32(shape[0]))
    return out


def rows(batch) -> int:
    return int(batch[0].shape[0])


def _layer_norm(x, scale, bias, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _row_blocks(fn, x):
    """``fn`` over blocks of the rows of ``x`` (N, d), each block its own
    checkpoint."""
    n, d = x.shape
    rb = _ROW_BLOCK if n % _ROW_BLOCK == 0 else n
    return jax.lax.map(jax.checkpoint(fn), x.reshape(n // rb, rb, d)).reshape(n, -1)


def _selective_scan(u, dt, a, bm, cm, dvec):
    """``s_t = h_t C_t + D u_t`` with ``h_t = exp(dt_t A) h_{t-1} + (dt_t
    u_t) B_t^T``, one step of time after another.  u, dt (B, T, d_in); a
    (d_in, N); bm, cm (B, T, N)."""
    b, t, d_in = u.shape

    def step(h, xs):
        u_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[..., None] * a) * h + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) + dvec * u_t

    chunk = _SCAN_CHUNK if t % _SCAN_CHUNK == 0 else t

    @jax.checkpoint
    def run(h, xs):
        return jax.lax.scan(step, h, xs)

    xs = tuple(jnp.moveaxis(v, 1, 0).reshape(t // chunk, chunk, b, -1)
               for v in (u, dt, bm, cm))
    _, s = jax.lax.scan(run, jnp.zeros((b, d_in, a.shape[1]), jnp.float32), xs)
    return jnp.moveaxis(s.reshape(t, b, d_in), 0, 1)


def _mamba(mm, u, p, model):
    """``(out, s)`` of the Mamba mixer on its normed input ``u`` (B, T, d)."""
    _, _, n, kc, r = _sizes(model)
    t = u.shape[1]
    xs = mm("btd,de->bte", u, p["ssm/in_x/kernel"])
    z = mm("btd,de->bte", u, p["ssm/in_z/kernel"])
    padded = jnp.pad(xs, ((0, 0), (kc - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * p["ssm/conv/kernel"][j] for j in range(kc))
    xc = jax.nn.silu(conv + p["ssm/conv/bias"])
    proj = mm("bte,ef->btf", xc, p["ssm/x_proj/kernel"])
    rr, bm, cm = proj[..., :r], proj[..., r:r + n], proj[..., r + n:]
    dt = jax.nn.softplus(mm("btr,re->bte", rr, p["ssm/dt_proj/kernel"])
                         + p["ssm/dt_proj/bias"])
    s = _selective_scan(xc, dt, -jnp.exp(p["ssm/A_log"]), bm, cm, p["ssm/D"])
    return mm("bte,ed->btd", s * jax.nn.silu(z), p["ssm/out_proj/kernel"]), s


def _diff_attention(mm, q, k, v, lam, window: int):
    """``(A1 - lam A2) [v1, v2]`` for every query pair: q (B, T, H, dh),
    k, v (B, T, Hkv, dh) -> (B, T, H / 2, 2 dh).  One K/V pair and one
    block of query rows at a time."""
    b, t, h, dh = q.shape
    pairs, kvp = h // 2, k.shape[2] // 2
    g = pairs // kvp
    qb = _Q_BLOCK if t % _Q_BLOCK == 0 else t
    nb = t // qb
    # (kvp * nb, b, qb, g, 2, dh): a K/V pair's query pairs, a block of rows
    qs = q.reshape(b, nb, qb, kvp, g, 2, dh).transpose(3, 1, 0, 2, 4, 5, 6)
    qs = qs.reshape(kvp * nb, b, qb, g, 2, dh)
    ks = k.reshape(b, t, kvp, 2, dh).transpose(2, 0, 1, 3, 4)        # (kvp, b, t, 2, dh)
    vs = v.reshape(b, t, kvp, 2 * dh).transpose(2, 0, 1, 3)          # (kvp, b, t, 2 dh)
    kpos = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        qi, j, i = args
        kj, vj = ks[j], vs[j]
        qpos = i * qb + jnp.arange(qb)
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window

        def probs(half):
            s = mm("bqgd,bkd->bgqk", qi[..., half, :], kj[..., half, :])
            s = s / jnp.sqrt(jnp.float32(dh))
            return jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)

        return (mm("bgqk,bke->bqge", probs(0), vj)
                - lam * mm("bgqk,bke->bqge", probs(1), vj))

    idx = jnp.arange(kvp * nb)
    o = jax.lax.map(one, (qs, idx // nb, idx % nb))     # (kvp * nb, b, qb, g, 2 dh)
    o = o.reshape(kvp, nb, b, qb, g, 2 * dh).transpose(2, 1, 3, 0, 4, 5)
    return o.reshape(b, t, pairs, 2 * dh)


def _attention(mm, u, p, a, model, index, window, kv=None):
    """``(out, (k, v))`` of a differential-attention mixer named ``a`` on
    its normed input ``u``; ``kv`` given, the layer attends those."""
    b, t, _ = u.shape
    h, hkv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]

    def proj(name, heads):
        y = mm("btd,de->bte", u, p[f"{a}/{name}/kernel"]) + p[f"{a}/{name}/bias"]
        return y.reshape(b, t, heads, dh)

    q = proj("q", h)
    k, v = kv if kv is not None else (proj("k", hkv), proj("v", hkv))
    lam0 = lam0_of(index)
    lam = (jnp.exp(jnp.sum(p[f"{a}/lambda_q1"] * p[f"{a}/lambda_k1"]))
           - jnp.exp(jnp.sum(p[f"{a}/lambda_q2"] * p[f"{a}/lambda_k2"])) + lam0)
    o = _diff_attention(mm, q, k, v, lam, window)
    o = _rms(o, p[f"{a}/subln/scale"], model["norm_eps"]) * (1.0 - lam0)
    out = mm("bte,ed->btd", o.reshape(b, t, h * dh), p[f"{a}/out/kernel"])
    return out + p[f"{a}/out/bias"], (k, v)


def _hidden(params, inputs, model, precision):
    """``(mm, x)``: the precision's product and the final-normed hidden
    states (B * T, d) of ``inputs``."""
    cast, prec, round_back = precision
    eps = model["norm_eps"]

    def mm(eq, a, b):
        return round_back(jnp.einsum(eq, cast(a), cast(b), precision=prec,
                                     preferred_element_type=jnp.float32))

    x = jnp.take(params["embed/embedding"], inputs, axis=0)
    b, t, d = x.shape

    def layer(i, kind):
        index = model["layer_indices"][i]

        def run(x, p, m, kv):
            u = _layer_norm(x, p["norm_attn/scale"], p["norm_attn/bias"], eps)
            keep = None
            if kind == "mamba":
                y, keep = _mamba(mm, u, p, model)
            elif kind == "gmu":
                gate = jax.nn.silu(mm("btd,de->bte", u, p["gmu/in_proj/kernel"]))
                y = mm("bte,ed->btd", m * gate, p["gmu/out_proj/kernel"])
            elif kind == "cross_attention":
                y, _ = _attention(mm, u, p, "xattn", model, index, 0, kv)
            else:
                window = model["sliding_window"] if kind == "sliding_attention" else 0
                y, keep = _attention(mm, u, p, "attn", model, index, window)
            x = x + y
            u = _layer_norm(x, p["norm_mlp/scale"], p["norm_mlp/bias"], eps)

            def mlp(rows_):
                gate = jax.nn.silu(mm("nd,df->nf", rows_, p["mlp/wg/kernel"]))
                return mm("nf,fd->nd", gate * mm("nd,df->nf", rows_, p["mlp/wi/kernel"]),
                          p["mlp/wo/kernel"])

            return x + _row_blocks(mlp, u.reshape(b * t, d)).reshape(b, t, d), keep

        return jax.checkpoint(run)

    m = kv = None
    for i, kind in enumerate(model["layer_types"]):
        prefix = f"block{i}/"
        p = {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}
        x, keep = layer(i, kind)(x, p, m, kv)
        if kind == "mamba":
            m = keep
        elif kind == "full_attention":
            kv = keep
    x = _layer_norm(x, params["norm_f/scale"], params["norm_f/bias"], eps)
    return mm, x.reshape(b * t, d)


def forward_logits(params, inputs, model, precision):
    """(B, T, V) logits, whole (the tests' sizes): what the loss below
    takes block by block."""
    mm, x = _hidden(params, inputs, model, precision)
    return mm("nd,vd->nv", x, params["embed/embedding"]).reshape(*inputs.shape, -1)


def _forward_loss(params, inputs, targets, model, precision):
    mm, x = _hidden(params, inputs, model, precision)
    n, d = x.shape
    rb = _ROW_BLOCK if n % _ROW_BLOCK == 0 else n

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
    is taken and not used: a batch of 2 has no blocks of batch rows to go
    through; what bounds memory here is the blocking set out above."""
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
