"""Ops: normalize (jnp + pallas-interpret parity), loss functions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.ops import cross_entropy_loss, normalize_images, softmax_cross_entropy


def test_normalize_range_and_dtype():
    imgs = np.array([[[[0, 128, 255]]]], np.uint8)
    out = normalize_images(jnp.asarray(imgs), jnp.float32)
    np.testing.assert_allclose(np.asarray(out), [[[[0.0, 128 / 255, 1.0]]]], atol=1e-7)
    assert out.dtype == jnp.float32


def test_pallas_normalize_matches_reference():
    from ddl_tpu.ops.pallas_image import pallas_normalize_images

    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.integers(0, 255, (4, 16, 16, 3)), jnp.uint8)
    got = pallas_normalize_images(imgs, jnp.float32, interpret=True)
    want = normalize_images(imgs, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-7)


def test_pallas_normalize_nondivisible_block():
    from ddl_tpu.ops.pallas_image import pallas_normalize_images

    rng = np.random.default_rng(1)
    # F = 10*10*3 = 300, not a multiple of the 1536 block
    imgs = jnp.asarray(rng.integers(0, 255, (2, 10, 10, 3)), jnp.uint8)
    got = pallas_normalize_images(imgs, jnp.float32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(normalize_images(imgs, jnp.float32)), atol=1e-7
    )


def test_cross_entropy_matches_torch():
    torch = pytest.importorskip("torch")
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(32, 5)).astype(np.float32)
    labels = rng.integers(0, 5, 32)
    want = torch.nn.functional.cross_entropy(
        torch.tensor(logits), torch.tensor(labels)
    ).item()
    got = float(cross_entropy_loss(jnp.asarray(logits), jnp.asarray(labels)))
    assert got == pytest.approx(want, rel=1e-5)


def test_softmax_cross_entropy_gradient_is_softmax_minus_onehot():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, 0.5]])
    labels = jnp.asarray([2])
    g = jax.grad(lambda l: softmax_cross_entropy(l, labels).sum())(logits)
    p = np.exp(np.asarray(logits[0]))
    p /= p.sum()
    p[2] -= 1
    np.testing.assert_allclose(np.asarray(g[0]), p, atol=1e-6)


class TestGroupedDenseAttention:
    def test_grouped_matches_repeated_kv(self):
        """GQA grouping == materially repeating each K/V head over its
        query group (the definition), causal and masked variants."""
        from ddl_tpu.ops.attention import dense_attention

        rng = np.random.default_rng(0)
        b, t, h, hkv, d = 2, 8, 6, 2, 4
        q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
        grouped = dense_attention(q, k, v, causal=True)
        repeated = dense_attention(
            q, jnp.repeat(k, h // hkv, 2), jnp.repeat(v, h // hkv, 2),
            causal=True,
        )
        np.testing.assert_allclose(
            np.asarray(grouped), np.asarray(repeated), atol=1e-6
        )

    def test_indivisible_heads_raise(self):
        from ddl_tpu.ops.attention import dense_attention

        q = jnp.zeros((1, 4, 6, 4))
        kv = jnp.zeros((1, 4, 4, 4))
        with pytest.raises(ValueError, match="divide"):
            dense_attention(q, kv, kv, causal=True)


class TestFusedChunkedCE:
    """Chunked head+CE fusion (ops/losses.fused_chunked_ce): exact parity
    with head-matmul + dense CE, in values AND gradients, without ever
    materialising (B, T, V) logits (VERDICT round 2, task 3)."""

    def _setup(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        b, t, d, v = 2, 32, 16, 97  # odd vocab: no tiling luck
        h = jnp.asarray(rng.normal(size=(b, t, d)), jnp.float32)
        # vocab-major kernel, as LMHead stores it
        w = jnp.asarray(rng.normal(size=(v, d)) * 0.1, jnp.float32)
        tg = jnp.asarray(rng.integers(0, v, (b, t)))
        return h, w, tg

    def _dense(self, h, w, tg):
        from ddl_tpu.ops.losses import cross_entropy_loss

        return cross_entropy_loss(h.astype(np.float32) @ w.T, tg)

    @pytest.mark.parametrize("chunk", [4, 8, 32, 100])
    @pytest.mark.parametrize("use_onehot", [False, True])
    def test_value_and_grad_parity(self, chunk, use_onehot):
        import jax
        import jax.numpy as jnp

        from ddl_tpu.ops.losses import fused_chunked_ce

        h, w, tg = self._setup()
        ce, acc = fused_chunked_ce(
            h, w, tg, chunk, with_accuracy=True, use_onehot=use_onehot
        )
        want = self._dense(h, w, tg)
        np.testing.assert_allclose(float(ce), float(want), atol=1e-5)
        logits = np.asarray(h) @ np.asarray(w).T
        np.testing.assert_allclose(
            float(acc), float(np.mean(logits.argmax(-1) == np.asarray(tg))),
            atol=1e-7,
        )
        gh, gw = jax.grad(
            lambda a, b: fused_chunked_ce(a, b, tg, chunk,
                                          use_onehot=use_onehot)[0],
            (0, 1),
        )(h, w)
        rh, rw = jax.grad(lambda a, b: self._dense(a, b, tg), (0, 1))(h, w)
        np.testing.assert_allclose(np.asarray(gh), np.asarray(rh), atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), atol=1e-5)

    def test_rejects_bad_chunk(self):
        from ddl_tpu.ops.losses import fused_chunked_ce

        h, w, tg = self._setup()
        with pytest.raises(ValueError, match="token_chunk"):
            fused_chunked_ce(h, w, tg, 0)

    def test_non_divisor_chunk_warns_and_picks_largest_divisor(self):
        import warnings

        from ddl_tpu.ops.losses import fused_chunked_ce

        h, w, tg = self._setup()  # T=32
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            ce, _ = fused_chunked_ce(h, w, tg, 24)  # largest divisor: 16
        assert any("largest divisor 16" in str(r.message) for r in rec)
        np.testing.assert_allclose(
            float(ce), float(self._dense(h, w, tg)), atol=1e-5
        )


class TestFusedVocabChunkedCE:
    """Vocab-streamed head+CE (ops/losses.fused_vocab_chunked_ce): exact
    value/grad/accuracy parity with dense CE while the (B, T, V) logits
    never exist in either direction (the extreme-vocab loss edge; PERF.md
    round 4 records it ~5% slower than dense at V=50k b=16 — the lever
    is memory, not rate)."""

    def _setup(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(1)
        b, t, d, v = 2, 24, 12, 90
        h = jnp.asarray(rng.normal(size=(b, t, d)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(v, d)) * 0.1, jnp.float32)
        tg = jnp.asarray(rng.integers(0, v, (b, t)))
        return h, w, tg

    def _dense(self, h, w, tg):
        from ddl_tpu.ops.losses import cross_entropy_loss

        return cross_entropy_loss(h.astype(np.float32) @ w.T, tg)

    @pytest.mark.parametrize("vb", [15, 30, 90, 1000])
    def test_value_grad_and_accuracy_parity(self, vb):
        import jax

        from ddl_tpu.ops.losses import fused_vocab_chunked_ce

        h, w, tg = self._setup()
        ce, acc = fused_vocab_chunked_ce(h, w, tg, vb, True)
        np.testing.assert_allclose(
            float(ce), float(self._dense(h, w, tg)), atol=1e-5
        )
        logits = np.asarray(h) @ np.asarray(w).T
        np.testing.assert_allclose(
            float(acc), float(np.mean(logits.argmax(-1) == np.asarray(tg))),
            atol=1e-7,
        )
        gh, gw = jax.grad(
            lambda a, b: fused_vocab_chunked_ce(a, b, tg, vb)[0], (0, 1)
        )(h, w)
        rh, rw = jax.grad(lambda a, b: self._dense(a, b, tg), (0, 1))(h, w)
        np.testing.assert_allclose(np.asarray(gh), np.asarray(rh), atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), atol=1e-5)

    def test_upstream_gradient_scales(self):
        """The custom VJP must respect a non-unit upstream cotangent."""
        import jax

        from ddl_tpu.ops.losses import fused_vocab_chunked_ce

        h, w, tg = self._setup()
        g3 = jax.grad(
            lambda a: 3.0 * fused_vocab_chunked_ce(a, w, tg, 30)[0]
        )(h)
        g1 = jax.grad(
            lambda a: fused_vocab_chunked_ce(a, w, tg, 30)[0]
        )(h)
        np.testing.assert_allclose(
            np.asarray(g3), 3 * np.asarray(g1), rtol=1e-5
        )


def test_interpret_default_is_one_rule_for_every_kernel():
    """Interpreted on the CPU backend, compiled on a TPU, and an error —
    never a silent interpreter — on anything else; every kernel module
    resolves ``interpret=None`` through the same helper."""
    import importlib

    import pytest

    from ddl_tpu.ops.interpret import interpret_default

    assert interpret_default("cpu") is True
    assert interpret_default("tpu") is False
    assert interpret_default() is True  # this suite runs on the CPU backend
    for platform in ("gpu", "some-plugin", ""):
        with pytest.raises(RuntimeError, match="neither"):
            interpret_default(platform)
    for name in ("flash_attention", "decode_attention", "fused_dense_block",
                 "int8_matvec", "pallas_image"):
        mod = importlib.import_module(f"ddl_tpu.ops.{name}")
        assert mod.interpret_default is interpret_default
