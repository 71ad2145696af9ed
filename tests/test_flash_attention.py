"""Pallas flash attention vs dense softmax attention (fwd + grad parity).

Runs in interpreter mode on CPU; the identical kernel compiles on TPU.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.ops.attention import dense_attention
from ddl_tpu.ops.flash_attention import flash_attention

# the module itself: ``ddl_tpu.ops`` re-exports the function under its name
fa = sys.modules["ddl_tpu.ops.flash_attention"]

# (block_q, block_k, sub-tile edge): None leaves the kernel's own rule, under
# which a tile this small is one sub-tile; an edge walks the resident tile in
# edge x edge sub-tiles.  CELL is the benchmark cell's shape class (T ==
# block_q == block_k, 4 x 4 sub-tiles in the one tile) scaled down by 16,
# TWO_Q the same with two Q blocks (the default before PR 26).
CELL = (64, 64, 16)
TWO_Q = (32, 64, 16)


def _set_edge(monkeypatch, edge):
    monkeypatch.setattr(fa, "_SUB_TILE", edge)
    monkeypatch.setattr(fa, "_SUB_TILE_LONG", edge)


@pytest.fixture
def blocks(request, monkeypatch):
    """Block arguments of one case; sets the sub-tile edge it asks for."""
    if isinstance(request.param, int):
        return dict(block_q=request.param, block_k=request.param)
    bq, bk, edge = request.param
    if edge is not None:
        _set_edge(monkeypatch, edge)
    return dict(block_q=bq, block_k=bk)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    shape = (2, 64, 3, 16)  # (B, T, H, D)
    return tuple(
        jnp.asarray(rng.normal(size=shape), jnp.float32) for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "blocks", [16, 32, 64, CELL, TWO_Q, (32, 64, 8), (64, 32, 16)], indirect=True
)
def test_flash_matches_dense_forward(qkv, causal, blocks):
    q, k, v = qkv
    out = flash_attention(q, k, v, causal=causal, **blocks)
    want = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=1e-4)


# (causal, window): no band, the causal band, and a window that is / is not
# a multiple of every sub-tile edge the cases below walk (8 and 16)
BANDS = [(False, 0), (True, 0), (True, 16), (True, 20)]


@pytest.mark.parametrize("causal,window", BANDS)
@pytest.mark.parametrize(
    "blocks",
    [(16, 32, None), CELL, TWO_Q, (64, 32, 8), (16, 16, 8), (32, 16, None)],
    indirect=True,
)
def test_flash_matches_dense_grads(qkv, causal, window, blocks):
    """dQ, dK and dV of the one backward kernel against the dense
    reference.  The block cases cover its grid: one tile (CELL), dQ
    crossing K steps (one Q block, two K blocks), dK/dV crossing Q steps
    (TWO_Q) and both at once, with and without sub-tiles in the tile."""
    q, k, v = qkv
    rng = np.random.default_rng(1)
    cot = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, window=window, **blocks)
        return (out * cot).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal=causal, window=window) * cot).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5, rtol=1e-4, err_msg=name
        )


@pytest.mark.parametrize("window", [0, 16], ids=["causal", "window16"])
def test_flash_with_a_wider_v_head_matches_dense(window):
    """V's head twice as wide as Q's and K's (differential attention's
    ``[v1, v2]``), grouped: the output and dV take V's width, forward and
    every gradient agree with the dense core."""
    ks = jax.random.split(jax.random.key(4), 4)
    q = jax.random.normal(ks[0], (2, 64, 4, 8))
    k = jax.random.normal(ks[1], (2, 64, 2, 8))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    w = jax.random.normal(ks[3], (2, 64, 4, 16))
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=window, block_q=32, block_k=32)
    dense = lambda q, k, v: dense_attention(q, k, v, causal=True, window=window)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        out = flash(q, k, v)
        assert out.shape == (2, 64, 4, 16)
        np.testing.assert_allclose(out, dense(q, k, v), rtol=1e-5, atol=1e-5)
        got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


def test_flash_mismatched_block_sizes_clamp():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 48, 2, 8)), jnp.float32)  # T=48
    out = flash_attention(q, q, q, causal=True)  # blocks clamp the 512 default -> 48
    want = dense_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=1e-4)


def test_lm_flash_matches_dense_model():
    """flash=True reproduces the plain model, standalone and with Ulysses."""
    import optax

    from ddl_tpu.models.transformer import LMConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns

    def run(spec, **cfg_kw):
        cfg = LMConfig(
            vocab_size=32, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, compute_dtype="float32", remat=False, **cfg_kw,
        )
        fns = make_lm_step_fns(
            cfg, spec, optax.adam(1e-3), jax.random.key(0), 4, 16
        )
        rng = np.random.default_rng(0)
        x = rng.integers(0, 32, (4, 17))
        state, m = fns.train(
            fns.init_state(), jnp.asarray(x[:, :-1]), jnp.asarray(x[:, 1:])
        )
        return float(m["loss"])

    ref = run(LMMeshSpec())
    flash_1dev = run(LMMeshSpec(data=2, model=2), flash=True)
    flash_uly = run(
        LMMeshSpec(data=2, seq=2, model=2), attn_impl="ulysses", flash=True
    )
    np.testing.assert_allclose(ref, flash_1dev, atol=1e-4)
    np.testing.assert_allclose(ref, flash_uly, atol=1e-4)


def test_lm_flash_rejects_bad_combos():
    import optax

    from ddl_tpu.models.transformer import LMConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns

    base = dict(
        vocab_size=32, d_model=32, n_layers=1, n_heads=4, head_dim=8,
        d_ff=64, compute_dtype="float32", remat=False, flash=True,
    )
    # flash + ring is no longer an error: the per-device blocks run
    # through the kernel (flash inside ring, see
    # test_ring_flash_matches_ring_dense / test_lm_ring_flash_matches_dense)
    with pytest.raises(ValueError, match="ulysses"):
        make_lm_step_fns(
            LMConfig(**base, attn_impl="dense"), LMMeshSpec(seq=2),
            optax.adam(1e-3), jax.random.key(0), 4, 16,
        )


def test_flash_bf16_finite():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.bfloat16)
    out = flash_attention(q, q, q, causal=True, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


def test_flash_auto_resolution():
    """flash="auto" picks the kernel only past the measured train-step
    crossover (PERF.md: dense wins at T=512, flash from T=1024) and only
    where the composition supports it."""
    import dataclasses

    from ddl_tpu.models.transformer import LMConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import FLASH_AUTO_MIN_T, resolve_auto_flash

    base = LMConfig(
        vocab_size=32, d_model=32, n_layers=1, n_heads=4, head_dim=8,
        d_ff=64, flash="auto",
    )
    spec = LMMeshSpec()
    assert resolve_auto_flash(base, spec, FLASH_AUTO_MIN_T - 1) is False
    assert resolve_auto_flash(base, spec, FLASH_AUTO_MIN_T) is True
    # ring auto is thresholded on the PER-DEVICE block: flash-in-ring from
    # T_local >= 2048 (device-only kernel crossover), dense blocks below
    ring = dataclasses.replace(base, attn_impl="ring")
    assert resolve_auto_flash(ring, LMMeshSpec(seq=2), 8192) is True
    assert resolve_auto_flash(ring, LMMeshSpec(seq=2), 2048) is False
    assert resolve_auto_flash(ring, LMMeshSpec(seq=4), 8192) is True
    assert resolve_auto_flash(ring, LMMeshSpec(seq=8), 8192) is False
    # degenerate seq=1 ring == full-sequence kernel: the step-level 1024
    # crossover applies, not the per-hop one
    assert resolve_auto_flash(ring, LMMeshSpec(), 1024) is True
    assert resolve_auto_flash(ring, LMMeshSpec(), 512) is False
    # dense attention cannot see a sharded sequence: stays dense
    assert resolve_auto_flash(base, LMMeshSpec(seq=2), 8192) is False
    bidir = dataclasses.replace(base, causal=False)
    assert resolve_auto_flash(bidir, spec, 8192) is False
    # ulysses attends the full sequence per head group: supported
    uly = dataclasses.replace(base, attn_impl="ulysses")
    assert resolve_auto_flash(uly, LMMeshSpec(seq=2), 8192) is True
    # ...but only when the local heads split exactly over 'seq' in the
    # all-to-all; n_heads=4, model=2 leaves 2 local heads, seq=4 doesn't fit
    assert resolve_auto_flash(uly, LMMeshSpec(seq=4, model=2), 8192) is False
    # heads must shard over 'model' for the manual core: fall back to dense
    assert resolve_auto_flash(base, LMMeshSpec(model=3), 8192) is False


def test_flash_rejects_unknown_string():
    import optax

    from ddl_tpu.models.transformer import LMConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns

    cfg = LMConfig(
        vocab_size=32, d_model=32, n_layers=1, n_heads=4, head_dim=8,
        d_ff=64, compute_dtype="float32", remat=False, flash="off",
    )
    with pytest.raises(ValueError, match="flash must be"):
        make_lm_step_fns(
            cfg, LMMeshSpec(), optax.adam(1e-3), jax.random.key(0), 4, 16
        )


def test_flash_auto_short_seq_trains_dense():
    """auto at short T resolves to the dense path and steps fine — in
    particular the auto+ring composition must resolve instead of hitting
    the flash/ring ValueError."""
    import jax
    import numpy as np
    import optax

    from ddl_tpu.models.transformer import LMConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns

    for attn, spec in (("dense", LMMeshSpec()), ("ring", LMMeshSpec(seq=2))):
        cfg = LMConfig(
            vocab_size=32, d_model=32, n_layers=1, n_heads=4, head_dim=8,
            d_ff=64, compute_dtype="float32", remat=False,
            attn_impl=attn, flash="auto",
        )
        fns = make_lm_step_fns(
            cfg, spec, optax.adam(1e-3), jax.random.key(0), 4, 16,
        )
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, 32, (4, 17))
        )
        state, m = fns.train(fns.init_state(), toks[:, :-1], toks[:, 1:])
        assert np.isfinite(float(m["loss"]))


@pytest.mark.parametrize("kv_offset", [0, 8, 32])
@pytest.mark.parametrize("kv_heads", [2, 1])
def test_flash_with_lse_matches_dense_logsumexp(kv_offset, kv_heads):
    """flash_attention_with_lse: out == dense attention, lse == the true
    per-row logsumexp of the scaled scores; both differentiable including
    a nonzero lse cotangent (the ring-combination consumption pattern),
    also where the K/V block lies ``kv_offset`` positions back (a ring
    hop: part of the band at 8, all of it at T) and is shared by two
    query heads."""
    from ddl_tpu.ops.flash_attention import flash_attention_with_lse

    rng = np.random.default_rng(5)
    b, t, h, d = 2, 32, 2, 8
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    k, v = (
        jnp.asarray(rng.normal(size=(b, t, kv_heads, d)), jnp.float32)
        for _ in range(2)
    )
    visible = (np.arange(t)[None, :] - kv_offset) <= np.arange(t)[:, None]

    def dense_ref(q, k, v):
        k, v = (jnp.repeat(x, h // kv_heads, axis=2) for x in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
            jnp.asarray(d, jnp.float32)
        )
        s = jnp.where(jnp.asarray(visible)[None, None], s, -1e30)
        lse = jax.scipy.special.logsumexp(s, axis=-1)  # (B, H, T)
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v
        )
        return out, lse

    def flash(q, k, v):
        return flash_attention_with_lse(
            q, k, v, causal=True, block_q=16, block_k=16, kv_offset=kv_offset
        )

    out_f, lse_f = flash(q, k, v)
    out_d, lse_d = dense_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse_f), np.asarray(lse_d), atol=1e-5)

    # gradient parity with BOTH cotangents live (out and lse)
    co = jnp.asarray(rng.normal(size=out_d.shape), jnp.float32)
    cl = jnp.asarray(rng.normal(size=lse_d.shape), jnp.float32)

    def loss(attend):
        def f(q, k, v):
            o, l = attend(q, k, v)
            return (o * co).sum() + (l * cl).sum()
        return f

    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(dense_ref), argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-4, err_msg=name
        )


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_ring_dense(causal):
    """Flash-inside-ring == the dense-block ring over a 4-device seq mesh,
    forward and gradients."""
    from jax.sharding import Mesh

    from ddl_tpu.parallel.ring_attention import make_ring_self_attention

    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    rng = np.random.default_rng(7)
    b, t, h, d = 2, 64, 2, 8  # T_local = 16
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
        for _ in range(3)
    )
    dense_ring = make_ring_self_attention(mesh, causal=causal)
    flash_ring = make_ring_self_attention(
        mesh, causal=causal, use_flash=True, flash_block=16
    )
    np.testing.assert_allclose(
        np.asarray(flash_ring(q, k, v)), np.asarray(dense_ring(q, k, v)),
        atol=1e-5,
    )
    co = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    gd = jax.grad(lambda *a: (dense_ring(*a) * co).sum(), (0, 1, 2))(q, k, v)
    gf = jax.grad(lambda *a: (flash_ring(*a) * co).sum(), (0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)


def test_lm_ring_flash_matches_dense():
    """Full LM train step: attn_impl='ring' + flash=True == flash=False
    (same gradients) on a (data=2, seq=2) mesh."""
    import optax

    from ddl_tpu.models.transformer import LMConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns

    states = {}
    for flash in (False, True):
        cfg = LMConfig(
            vocab_size=32, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, compute_dtype="float32", remat=False,
            attn_impl="ring", flash=flash,
        )
        fns = make_lm_step_fns(
            cfg, LMMeshSpec(data=2, seq=2), optax.adam(1e-3),
            jax.random.key(0), 4, 32, devices=jax.devices()[:4],
        )
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, 32, (4, 33))
        )
        s1, m = fns.train(fns.init_state(), toks[:, :-1], toks[:, 1:])
        states[flash] = (float(m["loss"]), jax.device_get(s1.params))
    assert abs(states[False][0] - states[True][0]) < 1e-5
    err = jax.tree.reduce(max, jax.tree.map(
        lambda a, b: float(np.max(np.abs(a - b))),
        states[False][1], states[True][1]))
    assert err < 1e-4


@pytest.mark.parametrize("hkv", [2, 1], ids=["group4", "group8"])
@pytest.mark.parametrize("blocks", [32, (64, 128, 32)], indirect=True)
def test_flash_gqa_matches_dense_and_repeated(blocks, hkv):
    """Grouped K/V through the Pallas kernel: forward equals the grouped
    dense core; gradients equal the repeat-then-attend formulation with
    dK/dV accumulated over the query-head group at Hkv granularity, at 4
    and at Trinity-Mini's 8 query heads a K/V head.  At blocks of 32 the
    head's dK/dV stay resident over 4 K blocks while 4 Q blocks of each
    group member cross them; the second case walks sub-tiles inside the
    group walk."""
    from ddl_tpu.ops.attention import dense_attention

    rng = np.random.default_rng(12)
    b, t, hq, d = 2, 128, 8, 16
    g = hq // hkv
    q = jnp.asarray(rng.normal(size=(b, t, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
    for window in (0, 32, 40):
        out = flash_attention(q, k, v, causal=True, window=window, **blocks)
        ref = dense_attention(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4
        )

        def loss(a, bb, c):
            return flash_attention(
                a, bb, c, causal=True, window=window, **blocks
            ).astype(jnp.float32).sum()

        gq, gk, gv = jax.grad(loss, (0, 1, 2))(q, k, v)
        assert gk.shape == k.shape  # gradients stay at Hkv heads
        rq, rk_rep, rv_rep = jax.grad(
            lambda a, bb, c: loss(
                a, jnp.repeat(bb, g, 2), jnp.repeat(c, g, 2)
            ),
            (0, 1, 2),
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), atol=2e-5)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(rk_rep), atol=2e-5)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(rv_rep), atol=2e-5)


def test_flash_gqa_lse_matches_repeated():
    from ddl_tpu.ops.flash_attention import flash_attention_with_lse

    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 8)), jnp.float32)
    o1, l1 = flash_attention_with_lse(q, k, v, causal=True, block_q=32, block_k=32)
    o2, l2 = flash_attention_with_lse(
        q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), causal=True,
        block_q=32, block_k=32,
    )
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-6)


def test_flash_rejects_bad_kv_heads():
    q = jnp.zeros((1, 32, 6, 8), jnp.float32)
    k = jnp.zeros((1, 32, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k, k, causal=True)


@pytest.mark.parametrize("blocks", [8, (16, 32, 8), (16, 16, 8)], indirect=True)
def test_flash_kv_offset_empty_band_rows_are_zero(blocks):
    """With kv_offset a live tile can hold rows whose whole band is masked;
    those rows must output exactly zero (and a floor lse), not mean-of-V
    garbage (round-3 review finding).  The later cases hold such rows
    inside a walked tile: in an edge sub-tile, and in sub-tiles the walk
    never visits."""
    from ddl_tpu.ops.flash_attention import flash_attention_with_lse

    rng = np.random.default_rng(3)
    t = 32
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, t, 2, 8)), jnp.float32)
        for _ in range(3)
    )
    # offset t, window 8: row q sees k_loc > q + t - 8, so rows >= 7
    # see nothing in this block (empty band inside a live tile)
    out, lse = flash_attention_with_lse(
        q, k, v, causal=True, window=8, kv_offset=t, **blocks
    )
    np.testing.assert_array_equal(np.asarray(out[:, 7:]), 0.0)
    assert np.all(np.asarray(lse[:, :, 7:]) < -1e29)
    # visible rows equal the dense cross-block band (the dense core's
    # fully-masked rows produce uniform-softmax output, so compare only
    # the rows with a non-empty band)
    pos_q = np.arange(t)[:, None]
    pos_k = np.arange(t)[None, :] - t
    mask = (pos_k <= pos_q) & (pos_k > pos_q - 8)
    want = dense_attention(q, k, v, mask=jnp.asarray(mask))
    got = np.asarray(out[:, :7])
    np.testing.assert_allclose(got, np.asarray(want)[:, :7], atol=2e-5)
    # backward stays finite and zero for the empty rows, and they add
    # nothing to dK and dV: all three equal the dense band's over the rows
    # that see a key
    g = jax.grad(
        lambda *x: flash_attention_with_lse(
            *x, causal=True, window=8, kv_offset=t, **blocks
        )[0].sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    assert all(bool(jnp.isfinite(x).all()) for x in g)
    np.testing.assert_array_equal(np.asarray(g[0][:, 7:]), 0.0)
    gd = jax.grad(
        lambda *x: dense_attention(*x, mask=jnp.asarray(mask))[:, :7].sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for got, want in ((g[0][:, :7], gd[0][:, :7]), (g[1], gd[1]), (g[2], gd[2])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _brute_plan(t, sub_q, sub_k, bk, causal, window, kv_offset):
    """Sub-tile counts from ``_qk_live`` over single (row, key) pairs, and
    the (row, K step) pairs: a Q sub-block's rows for every K block that
    holds a pair they see (the kernels make one pass a run)."""
    rows, keys = np.arange(t)[:, None], np.arange(t)[None, :]
    pair = np.broadcast_to(
        fa._qk_live(rows, keys, 1, 1, causal, window, kv_offset), (t, t)
    )
    tiles = pair.reshape(t // sub_q, sub_q, t // sub_k, sub_k)
    live, full = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    meets = pair.reshape(t // sub_q, sub_q, t // bk, bk).any(axis=(1, 3))
    return {
        "total": live.size, "computed": int(live.sum()),
        "masked": int((live & ~full).sum()),
        "row_steps": int(meets.sum()) * sub_q,
    }


@pytest.mark.parametrize(
    "t,bq,bk,edge,causal,window,kv_offset",
    [
        (1024, 1024, 1024, 256, True, 0, 0),    # the benchmark cell
        (1024, 512, 1024, 256, True, 0, 0),
        (1024, 512, 1024, 256, False, 0, 0),
        (2048, 512, 1024, 256, True, 0, 0),
        (2048, 512, 1024, 256, True, 300, 0),   # both edges, off the boundaries
        (1024, 512, 1024, 256, True, 256, 0),   # the past edge on a boundary
        (1024, 512, 1024, 256, True, 257, 0),
        (1024, 512, 1024, 256, True, 255, 0),
        (512, 512, 512, 128, True, 200, 512),   # a ring hop: offset, empty rows
        (512, 512, 512, 128, True, 0, 1024),    # an old hop: wholly visible
        (64, 32, 64, 16, True, 0, 0),
        (64, 32, 64, 16, True, 17, 0),
        (64, 32, 64, 16, True, 31, 0),          # three edges and nothing between
        (64, 32, 64, 16, True, 45, 0),
        (64, 64, 64, 16, True, 31, 64),
        (48, 512, 1024, 256, True, 0, 0),       # blocks clamp to T: one sub-tile
    ],
)
def test_flash_tile_plan_is_the_brute_force_count_and_the_kernels_walk(
    monkeypatch, t, bq, bk, edge, causal, window, kv_offset
):
    """``flash_tile_plan`` equals a count of ``_qk_live`` over element
    pairs (``masked`` may add the neighbour an unaligned edge can reach),
    and the kernels' walk (``_visible_run``, ``_masked``: run here outside
    a kernel on the same scalars) visits exactly the sub-tiles that hold a
    visible pair and masks every one the band's edge crosses."""
    _set_edge(monkeypatch, edge)
    plan = fa.flash_tile_plan(t, bq, bk, causal, window, kv_offset)
    bq, bk = fa._pick_block(t, bq), fa._pick_block(t, bk)
    sub_q, sub_k = plan["sub_tile"]
    assert (sub_q, sub_k) == fa._sub_tile(t, bq, bk, causal, window)
    want = _brute_plan(t, sub_q, sub_k, bk, causal, window, kv_offset)
    aligned = not window and kv_offset % sub_k == 0 and sub_q == sub_k
    for name in fa._KERNELS:
        got = plan[name]
        assert (got["total"], got["computed"]) == (want["total"], want["computed"])
        assert got["row_steps"] == want["row_steps"]
        assert want["masked"] <= got["masked"] <= got["computed"]
        assert got["masked"] == want["masked"] or not aligned
    if not causal:
        return  # no band, no walk: whole tiles (test_..._engages below)

    n = bk // sub_k
    reach = fa._edge_reach(sub_q, sub_k, bq, bk, kv_offset, window)
    run = jax.jit(
        lambda r0, k0: fa._visible_run(r0, sub_q, k0, sub_k, n, window)
    )
    runs = fa._grid_runs(t, bq, bk, sub_q, sub_k, window, kv_offset)
    visited = masked = 0
    for r0 in range(0, t, sub_q):
        for j in range(t // bk):
            k0 = j * bk - kv_offset
            lo, hi, future = map(int, run(jnp.int32(r0), jnp.int32(k0)))
            # the branch _visible selects exists: the kernel has code for
            # every run its grid reaches
            branches = fa._branches(runs, r0 % bq // sub_q)
            assert hi == lo or (lo, hi, hi < n or bool(future)) in branches
            bands = [
                fa._band(r0, sub_q, k0 + c * sub_k, sub_k, True, window)
                for c in range(n)
            ]
            assert set(range(lo, hi)) == {c for c, (live, _) in enumerate(bands) if live}
            # what _visible hands its step: the mask at the run's end
            # always when the run stops inside the block
            cols = {lo + e for e in fa._masked(hi - lo, reach, hi < n or bool(future))}
            edges = {c for c, (live, full) in enumerate(bands) if live and not full}
            assert edges <= cols and (cols == edges or not aligned)
            visited, masked = visited + hi - lo, masked + len(cols)
    assert (visited, masked) == (plan["flash_fwd"]["computed"], plan["flash_fwd"]["masked"])
    # one K step a run the grid reaches, of the Q sub-block's rows
    assert plan["flash_fwd"]["row_steps"] == sub_q * len(runs)


def test_flash_tile_plan_engages_in_the_benchmark_cell():
    """Causal, T == block_k (the defaults at T=1024): the grid's tile skip
    never fires there, the sub-tile walk must; and a non-causal call
    computes the whole square unmasked, in whole tiles.  The rule's other
    two arms: a long T and a window walk coarser squares."""
    plan = fa.flash_tile_plan(1024, causal=True)
    assert plan["sub_tile"] == [256, 256]
    # one forward and one backward kernel, the latter named for its grid
    assert fa._KERNELS == ("flash_fwd", "flash_bwd_dkv")
    assert sorted(plan) == ["flash_bwd_dkv", "flash_fwd", "sub_tile"]
    for name in fa._KERNELS:
        assert plan[name] == {"total": 16, "computed": 10, "masked": 4, "row_steps": 1024}
        assert plan[name]["computed"] <= 0.75 * plan[name]["total"]
    full = fa.flash_tile_plan(1024, causal=False)
    assert full["sub_tile"] == [1024, 1024]
    assert full["flash_fwd"] == {"total": 1, "computed": 1, "masked": 0, "row_steps": 1024}
    assert fa.flash_tile_plan(8192, causal=True)["sub_tile"] == [512, 512]
    assert fa.flash_tile_plan(1024, causal=True, window=256)["sub_tile"] == [512, 512]


@pytest.mark.parametrize(
    "rows,t,window,row_steps",
    [
        pytest.param(16 * 12, 1024, 0, 196_608, id="gpt2s"),
        pytest.param(2 * 32, 4096, 2048, 589_824, id="trinity-sliding"),
        pytest.param(2 * 32, 4096, 0, 655_360, id="trinity-full"),
        pytest.param(2 * 40, 4096, 512, 450_560, id="phi4flash-sliding"),
        pytest.param(2 * 40, 4096, 0, 819_200, id="phi4flash-full-and-cross"),
    ],
)
def test_flash_row_steps_a_call_in_the_benchmark_cells(rows, t, window, row_steps):
    """The (row, K step) pairs one call makes at the three LM cells'
    shapes, as each launcher writes them into its ``pallas_call``'s
    metadata: what the forward's time followed (ISSUE 32's counts)."""
    for name in fa._KERNELS:
        _, metadata = fa._walk(name, rows, t, 1024, 1024, True, window, 0)
        assert metadata["tiles_row_steps"] == str(row_steps)


# (T, blocks, band, kv_offset, K/V heads, V's width): a row makes up to four
# K steps (T = 4 x block_k), so its statistics cross grid steps and runs
SEVERAL_K_STEPS = [
    pytest.param(64, (16, 16, None), (True, 0), 0, 2, 8, id="causal"),
    pytest.param(64, (32, 16, 8), (True, 0), 0, 2, 8, id="causal-sub-tiles"),
    pytest.param(64, (16, 16, 8), (True, 16), 0, 2, 8, id="window-on-a-boundary"),
    pytest.param(64, (32, 16, 8), (True, 20), 0, 2, 8, id="window-off-a-boundary"),
    pytest.param(64, (16, 16, None), (False, 0), 0, 2, 8, id="non-causal"),
    pytest.param(64, (32, 16, 8), (True, 40), 64, 2, 8, id="kv-offset-empty-rows"),
    pytest.param(64, (16, 16, 8), (True, 0), 0, 1, 8, id="gqa"),
    pytest.param(64, (32, 16, 8), (True, 20), 0, 1, 16, id="gqa-wide-v"),
    pytest.param(1024, (512, 256, 128), (True, 300), 0, 1, 16, id="whole-lane-tiles"),
]


@pytest.mark.parametrize("t,blocks,band,kv_offset,hkv,dv", SEVERAL_K_STEPS, indirect=["blocks"])
def test_flash_over_several_k_steps_matches_dense(t, blocks, band, kv_offset, hkv, dv):
    """Out, lse and the three gradients against the dense reference where
    a row's running max and sum cross several K steps: the forward keeps
    them a lane tile wide (``m`` equal along a row's lanes, ``l`` as
    per-lane partial sums closed once a Q block), and ``lse`` keeps its
    values to float32 rounding, because the backward and the ring merge
    read it.  Rows with an empty band (``kv_offset``) read 0 and the floor."""
    from ddl_tpu.ops.flash_attention import flash_attention_with_lse

    causal, window = band
    h, d = 2, 8
    rng = np.random.default_rng(32)
    q = jnp.asarray(rng.normal(size=(1, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, t, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, hkv, dv)), jnp.float32)
    co = jnp.asarray(rng.normal(size=(1, t, h, dv)), jnp.float32)
    cl = jnp.asarray(rng.normal(size=(1, h, t)), jnp.float32)
    rows, keys = np.arange(t)[:, None], np.arange(t)[None, :]
    visible = np.broadcast_to(
        fa._qk_live(rows, keys, 1, 1, causal, window, kv_offset), (t, t)
    )
    seen = jnp.asarray(visible.any(axis=1))  # rows with a key in their band

    def dense(q, k, v):
        k, v = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
        s = jnp.where(jnp.asarray(visible)[None, None], s, -1e30)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        return out * seen[None, :, None, None], lse * seen

    def flash(q, k, v):
        out, lse = flash_attention_with_lse(
            q, k, v, causal=causal, window=window, kv_offset=kv_offset, **blocks
        )
        return out, lse * seen  # an empty row's lse is the floor: compared below

    def loss(attend):
        def f(q, k, v):
            out, lse = attend(q, k, v)
            return (out * co).sum() + (lse * cl).sum()
        return f

    with jax.default_matmul_precision("highest"):
        out, lse = flash_attention_with_lse(
            q, k, v, causal=causal, window=window, kv_offset=kv_offset, **blocks
        )
        want_out, want_lse = dense(q, k, v)
        got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    empty = ~np.asarray(seen)
    assert empty.any() == bool(kv_offset)
    np.testing.assert_array_equal(np.asarray(out)[:, empty], 0.0)
    assert np.all(np.asarray(lse)[:, :, empty] < -1e29)
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(lse * seen, want_lse, rtol=1e-6, atol=1e-6)
    for g, r, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5, err_msg=name)


def test_flash_backward_refuses_a_sequence_its_vmem_cannot_hold():
    """The backward keeps a K/V head's dK and dV in VMEM for the whole
    sequence; past what a core can hold it says so from the shapes (the
    forward has no such limit), and names the ring schedule."""
    q = jax.ShapeDtypeStruct((1, 65536, 1, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    jax.eval_shape(loss, q, q, q)
    with pytest.raises(ValueError, match="resident.*ring"):
        jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    half = jax.ShapeDtypeStruct((1, 32768, 1, 128), jnp.bfloat16)
    jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), half, half, half)
