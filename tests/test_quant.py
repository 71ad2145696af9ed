"""Int8 serving quantization (ops/quant.py): KV cache + weight-only.

Parity discipline: quantization is a *lossy* compression of HBM traffic,
so these tests pin the loss — element-wise error bounded by the absmax
scale, end-to-end logits within small relative error of the exact path,
and greedy decode agreeing on (almost) every token.  The exact-math
pieces (scale folding, ring slots, window slices, GQA grouping) are
tested exactly.  (The reference has no inference quantization — or any
generation path — at all; the bar here is this repo's own bf16 decode.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.infer import LMDecode, init_kv_cache, make_lm_generator
from ddl_tpu.models.transformer import LMConfig, TransformerLM
from ddl_tpu.ops.attention import dense_attention
from ddl_tpu.ops.quant import (
    QuantKV,
    dequantize_q8,
    quant_dense_attention,
    quantize_lm_params,
    quantize_q8,
)


def _cfg(**kw):
    base = dict(
        vocab_size=64,
        d_model=32,
        n_layers=2,
        n_heads=4,
        head_dim=8,
        d_ff=64,
        compute_dtype="float32",
        attn_impl="dense",
        remat=False,
    )
    base.update(kw)
    return LMConfig(**base)


def _params(cfg, batch=2, t=8, seed=0):
    import flax.linen as nn

    model = TransformerLM(cfg, None)
    dummy = jnp.zeros((batch, t), jnp.int32)
    return nn.meta.unbox(model.init(jax.random.key(seed), dummy)["params"])


def test_quantize_roundtrip_error_bound():
    """|x - dequant(quant(x))| <= scale/2 element-wise (round-to-nearest)."""
    x = jax.random.normal(jax.random.key(0), (4, 16, 3, 32)) * 3.0
    q, s = quantize_q8(x)
    err = np.abs(np.asarray(x) - np.asarray(dequantize_q8(q, s)))
    assert np.all(err <= np.asarray(s) / 2 + 1e-7)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32


def test_quant_attention_matches_dequantized_reference():
    """quant_dense_attention == dense_attention over the dequantized cache
    (same math, scales folded into scores/probs instead)."""
    rng = np.random.default_rng(0)
    b, tq, L, h, hkv, d = 2, 3, 16, 8, 4, 16
    q = jnp.asarray(rng.normal(size=(b, tq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, L, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, L, hkv, d)), jnp.float32)
    kq, ks = quantize_q8(k)
    vq, vs = quantize_q8(v)
    mask = jnp.asarray(rng.random((tq, L)) > 0.3)
    mask = mask.at[:, 0].set(True)  # no fully-masked row
    got = quant_dense_attention(
        q, kq, ks[..., 0].transpose(0, 2, 1), vq,
        vs[..., 0].transpose(0, 2, 1), mask,
    )  # scales are (B, Hkv, L) in cache layout
    want = dense_attention(
        q, dequantize_q8(kq, ks), dequantize_q8(vq, vs), mask=mask
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4
    )


def test_kv_quant_incremental_close_to_exact():
    """Token-by-token decode with the int8 cache tracks the full forward's
    logits within int8-level error at every position."""
    cfg = _cfg()
    b, t = 2, 7
    params = _params(cfg, b, t)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 64, (b, t)))
    ref_logits, _ = TransformerLM(cfg, None).apply({"params": params}, toks)

    dec = LMDecode(cfg)
    caches = init_kv_cache(cfg, b, t, quant=True)
    assert isinstance(caches[0].kv, QuantKV)
    got = []
    for i in range(t):
        logits, caches = dec.apply(
            {"params": params}, toks[:, i : i + 1], caches
        )
        got.append(np.asarray(logits[:, 0]))
    got = np.stack(got, 1)
    ref = np.asarray(ref_logits)
    # int8 cache error, bounded relative to the logit scale
    assert np.max(np.abs(got - ref)) / (np.abs(ref).max() + 1e-9) < 0.05
    # and the argmax (greedy token) agrees nearly everywhere
    agree = (got.argmax(-1) == ref.argmax(-1)).mean()
    assert agree >= 0.9


@pytest.mark.parametrize(
    "kw",
    [
        {},  # MHA, full cache
        {"n_kv_heads": 2},  # GQA
        {"attn_window": 6},  # windowed (rolling ring cache auto-on)
    ],
    ids=["mha", "gqa", "window"],
)
def test_kv_quant_generator_matches_bf16_generator(kw):
    """The full jitted generator (prefill + scan) with kv_quant=True
    produces (nearly) the same greedy tokens as the exact cache."""
    cfg = _cfg(**kw)
    b, p, n = 2, 8, 12
    params = _params(cfg, b, p)
    prompt = jnp.asarray(np.random.default_rng(2).integers(0, 64, (b, p)))
    gen = make_lm_generator(cfg, prompt_len=p, max_new=n, batch=b)
    gen_q = make_lm_generator(
        cfg, prompt_len=p, max_new=n, batch=b, kv_quant=True
    )
    t_ref = np.asarray(gen(params, prompt))
    t_q = np.asarray(gen_q(params, prompt))
    assert (t_ref == t_q).mean() >= 0.8, (t_ref, t_q)


def test_weight_quant_forward_close():
    """quantize_lm_params tree applies through the SAME modules (QDense /
    LMHead sniff the scale leaves) and tracks the f32 forward."""
    cfg = _cfg()
    b, t = 2, 8
    params = _params(cfg, b, t)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 64, (b, t)))
    qparams = quantize_lm_params(params)
    # every matmul kernel went int8 + scale; norms/embed/router untouched
    assert qparams["block0"]["attn"]["q"]["kernel"].dtype == jnp.int8
    assert qparams["block0"]["attn"]["q"]["scale"].shape == (
        1, cfg.n_heads * cfg.head_dim,
    )
    assert qparams["lm_head"]["kernel"].dtype == jnp.int8
    assert qparams["lm_head"]["scale"].shape == (cfg.vocab_size, 1)
    assert qparams["embed"]["embedding"].dtype == jnp.float32
    assert qparams["norm_f"]["scale"].dtype == jnp.float32

    ref, _ = TransformerLM(cfg, None).apply({"params": params}, toks)
    got, _ = TransformerLM(cfg, None).apply({"params": qparams}, toks)
    ref, got = np.asarray(ref), np.asarray(got)
    assert np.max(np.abs(got - ref)) / (np.abs(ref).max() + 1e-9) < 0.08
    assert (got.argmax(-1) == ref.argmax(-1)).mean() >= 0.9


def test_weight_quant_moe_forward_close():
    """Expert banks quantize per (expert, out-channel) and the MoE layer
    dequants via the wi_scale/wo_scale leaves."""
    cfg = _cfg(num_experts=4, expert_top_k=2, moe_group=0)
    b, t = 2, 8
    params = _params(cfg, b, t)
    toks = jnp.asarray(np.random.default_rng(4).integers(0, 64, (b, t)))
    qparams = quantize_lm_params(params)
    moe = qparams["block0"]["moe"]
    assert moe["wi"].dtype == jnp.int8
    assert moe["wi_scale"].shape == (4, 1, cfg.d_ff)
    assert moe["router"]["kernel"].dtype == jnp.float32  # routing exact

    ref, _ = TransformerLM(cfg, None).apply({"params": params}, toks)
    got, _ = TransformerLM(cfg, None).apply({"params": qparams}, toks)
    ref, got = np.asarray(ref), np.asarray(got)
    assert np.max(np.abs(got - ref)) / (np.abs(ref).max() + 1e-9) < 0.08


def test_weight_and_kv_quant_generator():
    """The full int8 serving path: int8 weights AND int8 cache through the
    jitted generator, vs the exact generator."""
    cfg = _cfg(n_kv_heads=2, attn_window=10)
    b, p, n = 2, 8, 12
    params = _params(cfg, b, p)
    prompt = jnp.asarray(np.random.default_rng(5).integers(0, 64, (b, p)))
    gen = make_lm_generator(cfg, prompt_len=p, max_new=n, batch=b)
    gen_q = make_lm_generator(
        cfg, prompt_len=p, max_new=n, batch=b, kv_quant=True
    )
    t_ref = np.asarray(gen(params, prompt))
    t_q = np.asarray(gen_q(quantize_lm_params(params), prompt))
    assert (t_ref == t_q).mean() >= 0.7, (t_ref, t_q)


def test_head_kernel_accessor_dequants():
    """The chunked-CE paths read the head kernel via ops.quant.head_kernel
    — on an int8 tree it must hand back the dequantized f32 kernel, not
    the raw int8 (which would silently drop the per-row scales)."""
    cfg = _cfg()
    from ddl_tpu.ops.quant import head_kernel

    params = _params(cfg)
    qparams = quantize_lm_params(params)
    got = head_kernel(qparams["lm_head"])
    ref = params["lm_head"]["kernel"]
    assert got.dtype == jnp.float32
    err = np.abs(np.asarray(got) - np.asarray(ref))
    assert err.max() <= np.asarray(qparams["lm_head"]["scale"]).max() / 2 + 1e-7
    # exact passthrough on an unquantized tree
    assert head_kernel(params["lm_head"]) is ref


def test_ce_chunk_eval_with_quantized_params():
    """Teacher-forced eval through the token-chunked CE edge on an int8
    tree matches the dense-CE eval of the same tree (the path the review
    flagged: the chunked edge bypasses LMHead's scale sniffing)."""
    cfg = _cfg(ce_chunk=4)
    b, t = 2, 8
    params = _params(cfg, b, t)
    qparams = quantize_lm_params(params)
    toks = jnp.asarray(np.random.default_rng(6).integers(0, 64, (b, t)))
    tgts = jnp.asarray(np.random.default_rng(7).integers(0, 64, (b, t)))
    from ddl_tpu.train.lm_steps import chunked_ce_loss
    from ddl_tpu.ops.quant import head_kernel

    hidden, aux = TransformerLM(cfg, None).apply(
        {"params": qparams}, toks, return_hidden=True
    )
    loss, _ = chunked_ce_loss(
        cfg, hidden, head_kernel(qparams["lm_head"]), tgts, aux, False
    )
    # dense-CE reference over the same quantized tree
    logits, _ = TransformerLM(
        dataclasses_replace(cfg, ce_chunk=0), None
    ).apply({"params": qparams}, toks)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    ref = -jnp.take_along_axis(lp, tgts[..., None], -1).mean()
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)


def dataclasses_replace(cfg, **kw):
    import dataclasses

    return dataclasses.replace(cfg, **kw)


def test_quantize_boxed_tree_and_empty_tree():
    """A fresh (boxed) init tree quantizes — no silent no-op — and a tree
    with nothing to quantize raises."""
    import flax.linen as nn

    cfg = _cfg()
    boxed = TransformerLM(cfg, None).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]  # NOT unboxed
    q = quantize_lm_params(boxed)
    assert q["block0"]["attn"]["q"]["kernel"].dtype == jnp.int8
    with pytest.raises(ValueError, match="no matmul kernel"):
        quantize_lm_params({"norm": {"scale": jnp.ones((4,))}})


def test_quant_cache_bytes_halved():
    """The allocation claim behind the bench rows: int8 cache bytes ≈
    0.53x bf16 (int8 payload + 1 f32 scale per head_dim values)."""
    cfg = _cfg(compute_dtype="bfloat16")
    bf16 = jax.eval_shape(lambda: [c.kv for c in init_kv_cache(cfg, 4, 128)])
    q8 = jax.eval_shape(
        lambda: [c.kv for c in init_kv_cache(cfg, 4, 128, quant=True)]
    )
    nbytes = lambda tree: sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(tree)
    )
    ratio = nbytes(q8) / nbytes(bf16)
    assert abs(ratio - (0.5 + 4 / (2 * cfg.head_dim))) < 1e-6
