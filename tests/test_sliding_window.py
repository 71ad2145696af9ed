"""Sliding-window attention (LMConfig.attn_window, the Mistral recipe):
band-masked causal attention across every core — dense, flash kernel
(block-skip), ring (global-position band across hops), Ulysses, and the
decode cache — all equal to the dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.ops.attention import dense_attention
from ddl_tpu.ops.flash_attention import flash_attention

W = 8


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    shape = (2, 64, 2, 8)
    return tuple(
        jnp.asarray(rng.normal(size=shape), jnp.float32) for _ in range(3)
    )


def _dense_banded(q, k, v, window):
    """Independent reference: explicit band mask fed to dense_attention."""
    t = q.shape[1]
    pos = np.arange(t)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - window)
    return dense_attention(q, k, v, mask=jnp.asarray(mask))


def test_dense_window_matches_explicit_band(qkv):
    q, k, v = qkv
    out = dense_attention(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense_banded(q, k, v, W)), atol=1e-6
    )
    # window >= T degenerates to plain causal
    full = dense_attention(q, k, v, causal=True, window=4096)
    plain = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(full), np.asarray(plain), atol=1e-6)
    with pytest.raises(ValueError, match="causal"):
        dense_attention(q, k, v, causal=False, window=W)
    # an explicit mask would silently override the band: reject the combo
    with pytest.raises(ValueError, match="explicit mask"):
        dense_attention(
            q, k, v, causal=True, window=W,
            mask=jnp.tril(jnp.ones((q.shape[1], q.shape[1]), bool)),
        )


@pytest.mark.parametrize(
    "window,bq,bk,edge",
    [
        (4, 16, 16, None), (8, 16, 16, None), (24, 16, 16, None),
        # the benchmark cell's shape class (T == block_k, two Q blocks)
        # walked in 16 x 16 sub-tiles: the band's past edge one before, on
        # and one after a sub-tile boundary, inside one sub-tile, and
        # across three
        (15, 32, 64, 16), (16, 32, 64, 16), (17, 32, 64, 16),
        (5, 32, 64, 16), (40, 32, 64, 16),
        (31, 32, 64, 16),  # a run of three sub-tiles, each crossed by an edge
        (17, 64, 64, 16),  # one Q block (the default's shape at T <= 1024)
    ],
)
def test_flash_window_matches_dense(qkv, monkeypatch, window, bq, bk, edge):
    """Band-masked kernel (incl. block skipping: window 4 < block 16 skips
    whole past blocks) == dense band, forward and gradients."""
    import sys

    if edge is not None:  # a windowed call walks the coarser edge
        monkeypatch.setattr(
            sys.modules["ddl_tpu.ops.flash_attention"], "_SUB_TILE_LONG", edge
        )
    q, k, v = qkv
    out = flash_attention(
        q, k, v, causal=True, window=window, block_q=bq, block_k=bk
    )
    want = _dense_banded(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)

    cot = jnp.asarray(np.random.default_rng(1).normal(size=q.shape), jnp.float32)
    gf = jax.grad(
        lambda *a: (flash_attention(
            *a, causal=True, window=window, block_q=bq, block_k=bk
        ) * cot).sum(),
        (0, 1, 2),
    )(q, k, v)
    gd = jax.grad(
        lambda *a: (_dense_banded(*a, window) * cot).sum(), (0, 1, 2)
    )(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_ring_window_matches_dense(qkv):
    """The ring's global-position band: window spans ring-block boundaries."""
    from jax.sharding import Mesh

    from ddl_tpu.parallel.ring_attention import make_ring_self_attention

    q, k, v = qkv
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    ring = make_ring_self_attention(mesh, causal=True, window=W)
    np.testing.assert_allclose(
        np.asarray(ring(q, k, v)), np.asarray(_dense_banded(q, k, v, W)),
        atol=1e-5,
    )


def test_ulysses_window_matches_dense(qkv):
    from jax.sharding import Mesh

    from ddl_tpu.parallel.ulysses import make_ulysses_self_attention

    q, k, v = qkv
    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    uly = make_ulysses_self_attention(mesh, causal=True, window=W)
    np.testing.assert_allclose(
        np.asarray(uly(q, k, v)), np.asarray(_dense_banded(q, k, v, W)),
        atol=1e-5,
    )


@pytest.mark.parametrize("n_dev,window", [(2, 8), (4, 8), (4, 24), (4, 100)])
def test_ring_flash_window_matches_dense(n_dev, window):
    """Flash-in-ring with a sliding window (the round-2 ValueError, now a
    feature): each hop runs the kernel banded in its own coordinates via
    kv_offset, the ring truncates to O(window) hops, and the result equals
    single-device banded attention — including windows smaller than,
    spanning, and exceeding the T_local block (and the full sequence)."""
    from jax.sharding import Mesh

    from ddl_tpu.parallel.ring_attention import make_ring_self_attention

    rng = np.random.default_rng(5)
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 32, 2, 8)), jnp.float32)
        for _ in range(3)
    )
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("seq",))
    fn = make_ring_self_attention(
        mesh, causal=True, use_flash=True, window=window, flash_block=8
    )
    want = _dense_banded(q, k, v, window)
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v)), np.asarray(want), atol=2e-5, rtol=1e-4
    )
    # differentiable (the training path)
    g = jax.grad(lambda a, b, c: fn(a, b, c).sum(), (0, 1, 2))(q, k, v)
    gd = jax.grad(
        lambda a, b, c: _dense_banded(a, b, c, window).sum(), (0, 1, 2)
    )(q, k, v)
    for a, b in zip(g, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5, rtol=1e-4
        )


def test_config_window_requires_causal():
    from ddl_tpu.models.transformer import LMConfig

    with pytest.raises(ValueError, match="attn_window"):
        LMConfig(causal=False, attn_window=W)
    with pytest.raises(ValueError, match=">= 0"):
        LMConfig(attn_window=-1)


def test_lm_ring_flash_window_matches_dense_model():
    """Full model: flash-in-ring + attn_window on a seq=2 mesh reproduces
    the single-device dense-windowed run (the round-2 factory ValueError
    is now a supported composition)."""
    import optax

    from ddl_tpu.models.transformer import LMConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns

    def run(spec, **kw):
        cfg = LMConfig(
            vocab_size=32, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, compute_dtype="float32", remat=False, attn_window=W,
            **kw,
        )
        fns = make_lm_step_fns(
            cfg, spec, optax.adam(1e-3), jax.random.key(0), 4, 32,
            devices=jax.devices()[: spec.num_devices],
        )
        rng = np.random.default_rng(0)
        x = rng.integers(0, 32, (4, 33))
        _, m = fns.train(
            fns.init_state(), jnp.asarray(x[:, :-1]), jnp.asarray(x[:, 1:])
        )
        return float(m["loss"])

    ref = run(LMMeshSpec())
    got = run(LMMeshSpec(seq=2), attn_impl="ring", flash=True)
    np.testing.assert_allclose(got, ref, atol=1e-4)


def test_lm_windowed_decode_matches_training_forward():
    """End to end: a windowed LM's cached incremental decode reproduces its
    training forward token by token (both paths apply the same band)."""
    from ddl_tpu.infer import LMDecode, init_kv_cache
    from ddl_tpu.models.transformer import LMConfig, TransformerLM

    cfg = LMConfig(
        vocab_size=32, d_model=16, n_layers=2, n_heads=2, head_dim=8,
        d_ff=32, compute_dtype="float32", remat=False, attn_window=4,
    )
    b, t = 2, 12  # window 4 << t: the band actually bites
    model = TransformerLM(cfg, None)
    import flax.linen as nn

    dummy = jnp.zeros((b, t), jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.key(0), dummy)["params"])
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 32, (b, t)))
    ref_logits, _ = model.apply({"params": params}, toks)

    # windowed must differ from unwindowed (sanity that the band applies)
    import dataclasses

    full_model = TransformerLM(dataclasses.replace(cfg, attn_window=0), None)
    full_logits, _ = full_model.apply({"params": params}, toks)
    assert float(np.abs(np.asarray(ref_logits - full_logits)).max()) > 1e-3

    caches = init_kv_cache(cfg, b, t)
    dec = LMDecode(cfg)
    for i in range(t):
        logits, caches = dec.apply(
            {"params": params}, toks[:, i : i + 1], caches
        )
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(ref_logits[:, i]), atol=1e-5
        )


def test_lm_windowed_training_sharded_matches_single():
    """Windowed LM under (data=2, seq=2) ring SP == single device."""
    import optax

    from ddl_tpu.models.transformer import LMConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns

    losses = {}
    for name, spec, attn in (
        ("single", LMMeshSpec(), "dense"),
        ("ring", LMMeshSpec(data=2, seq=2), "ring"),
        ("ulysses", LMMeshSpec(data=2, seq=2), "ulysses"),
    ):
        cfg = LMConfig(
            vocab_size=32, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, compute_dtype="float32", remat=False,
            attn_impl=attn, attn_window=8,
        )
        fns = make_lm_step_fns(
            cfg, spec, optax.adam(1e-3), jax.random.key(0), 4, 32,
            devices=jax.devices()[: spec.num_devices],
        )
        toks = jnp.asarray(np.random.default_rng(0).integers(0, 32, (4, 33)))
        _, m = fns.train(fns.init_state(), toks[:, :-1], toks[:, 1:])
        losses[name] = float(m["loss"])
    assert abs(losses["single"] - losses["ring"]) < 1e-4
    assert abs(losses["single"] - losses["ulysses"]) < 1e-4


def test_windowed_generation_matches_full_cache_model():
    """make_lm_generator with a windowed config: greedy generation through
    the O(window) cache slice equals greedy next-token argmax of the same
    windowed model's training forward at every step."""
    import flax.linen as nn

    from ddl_tpu.infer import make_lm_generator
    from ddl_tpu.models.transformer import LMConfig, TransformerLM

    cfg = LMConfig(
        vocab_size=32, d_model=16, n_layers=2, n_heads=2, head_dim=8,
        d_ff=32, compute_dtype="float32", remat=False, attn_window=4,
    )
    b, prompt_len, max_new = 2, 6, 8
    model = TransformerLM(cfg, None)
    params = nn.meta.unbox(
        model.init(jax.random.key(0), jnp.zeros((b, prompt_len), jnp.int32))
        ["params"]
    )
    prompt = jnp.asarray(
        np.random.default_rng(3).integers(0, 32, (b, prompt_len))
    )
    gen = make_lm_generator(
        cfg, prompt_len=prompt_len, max_new=max_new, batch=b
    )
    out = np.asarray(gen(params, prompt, jax.random.key(1)))

    # teacher-forcing reference: feed the growing sequence through the
    # training forward and take argmax of the last position each step
    seq = np.asarray(prompt)
    for i in range(max_new):
        logits, _ = model.apply({"params": params}, jnp.asarray(seq))
        nxt = np.argmax(np.asarray(logits[:, -1]), -1)
        np.testing.assert_array_equal(out[:, i], nxt, err_msg=f"step {i}")
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
