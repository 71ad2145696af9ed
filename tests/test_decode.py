"""KV-cached autoregressive decoding (infer/decode.py).

Parity discipline: incremental decode shares parameters with the training
model by construction, so its logits must match the full-sequence forward
bit-for-bit-close in f32 — both at prefill and after every cached step.
(The reference has no generation path at all; its only inference surface is
the loss-less eval schedule, ``pp.py:146-150``.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.infer import LMDecode, init_kv_cache, make_lm_generator
from ddl_tpu.models.transformer import LMConfig, TransformerLM
from ddl_tpu.parallel.sharding import LMMeshSpec


def _cfg(**kw):
    base = dict(
        vocab_size=32,
        d_model=16,
        n_layers=2,
        n_heads=2,
        head_dim=8,
        d_ff=32,
        compute_dtype="float32",
        attn_impl="dense",
        remat=False,
    )
    base.update(kw)
    return LMConfig(**base)


def _params(cfg, batch=2, t=8, seed=0):
    model = TransformerLM(cfg, None)
    dummy = jnp.zeros((batch, t), jnp.int32)
    import flax.linen as nn

    return nn.meta.unbox(model.init(jax.random.key(seed), dummy)["params"])


def test_prefill_matches_full_forward():
    """Prefill through the cache path == the training forward."""
    cfg = _cfg()
    b, p = 2, 6
    params = _params(cfg, b, p)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 32, (b, p)))

    ref_logits, _ = TransformerLM(cfg, None).apply({"params": params}, toks)

    caches = init_kv_cache(cfg, b, p + 2)
    dec_logits, _ = LMDecode(cfg).apply({"params": params}, toks, caches)
    np.testing.assert_allclose(
        np.asarray(ref_logits), np.asarray(dec_logits), atol=1e-5
    )


def test_incremental_matches_full_forward():
    """Token-by-token cached decode reproduces the full forward's logits at
    every position."""
    cfg = _cfg()
    b, t = 2, 7
    params = _params(cfg, b, t)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 32, (b, t)))

    ref_logits, _ = TransformerLM(cfg, None).apply({"params": params}, toks)

    dec = LMDecode(cfg)
    caches = init_kv_cache(cfg, b, t)
    got = []
    for i in range(t):
        logits, caches = dec.apply(
            {"params": params}, toks[:, i : i + 1], caches
        )
        got.append(logits[:, 0])
    np.testing.assert_allclose(
        np.asarray(ref_logits), np.stack([np.asarray(g) for g in got], 1),
        atol=1e-5,
    )


def _paged_after_prefill(cfg, caches, prompt_len, block_size, nmax):
    """What the serving engine does between its prefill and its decode
    chunk: each lane's contiguous prefill rows scattered into blocks of its
    own, the tables gathered once into the per-lane view."""
    from ddl_tpu.ops.quant import kv_map
    from ddl_tpu.serve.kv_pool import (
        PagedKV, init_kv_pool, pool_gather, pool_write_prefill,
    )

    b = caches[0].kv[0].shape[0]
    filled = caches[0].kv[0].shape[1] // block_size
    tables = jnp.arange(b * nmax, dtype=jnp.int32).reshape(b, nmax)
    lengths = jnp.full((b,), prompt_len, jnp.int32)
    paged = []
    for layer, pool in zip(caches, init_kv_pool(cfg, b * nmax, block_size)):
        for lane in range(b):
            row = kv_map(lambda a: a[lane:lane + 1], layer.kv)
            pool = pool_write_prefill(pool, row, tables[lane, :filled])
        paged.append(PagedKV(pool, pool_gather(pool, tables), tables, lengths))
    return tuple(paged)


@pytest.mark.parametrize(
    "kind,window",
    [("contiguous", 0), ("contiguous", 6), ("rolling", 6), ("paged", 0),
     ("paged", 6)],
)
def test_every_cache_kind_decodes_the_full_forwards_logits(kind, window):
    """The seam: one ``LMDecode`` over each kind of cache.  A prefill then
    eight single-token steps give the full forward's logits at the same
    positions — a linear buffer (whole, and its O(window) read slice), a
    ring shorter than the run, and the paged pool with blocks of 4 under a
    prompt of 5 (the last block half filled, lanes in blocks of their
    own)."""
    cfg = _cfg(attn_window=window)
    b, p, n, bs = 2, 5, 8, 4
    params = _params(cfg, b, p + n)
    toks = jnp.asarray(np.random.default_rng(4).integers(0, 32, (b, p + n)))
    ref, _ = TransformerLM(cfg, None).apply({"params": params}, toks)

    dec = LMDecode(cfg)
    if kind == "paged":
        caches = init_kv_cache(cfg, b, -(-p // bs) * bs)
    else:
        caches = init_kv_cache(cfg, b, p + n, rolling=kind == "rolling")
    logits, caches = dec.apply({"params": params}, toks[:, :p], caches)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[:, :p]), atol=1e-5)
    if kind == "paged":
        caches = _paged_after_prefill(cfg, caches, p, bs, nmax=4)
    for i in range(p, p + n):
        logits, caches = dec.apply({"params": params}, toks[:, i:i + 1], caches)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(ref[:, i]), atol=1e-5
        )
    if kind == "paged":
        with pytest.raises(ValueError, match="one new token a lane"):
            dec.apply({"params": params}, toks[:, :2], caches)


def test_greedy_generate_matches_teacher_forcing():
    """The jitted generate loop == a python loop re-running the full
    forward and taking argmax each step."""
    cfg = _cfg()
    b, p, n = 2, 4, 5
    params = _params(cfg, b, p)
    prompt = jnp.asarray(np.random.default_rng(2).integers(0, 32, (b, p)))

    model = TransformerLM(cfg, None)
    seq = prompt
    ref = []
    for _ in range(n):
        logits, _ = model.apply({"params": params}, seq)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        ref.append(np.asarray(tok))
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)

    gen = make_lm_generator(
        cfg, prompt_len=p, max_new=n, batch=b, devices=jax.devices()[:1]
    )
    out = np.asarray(gen(params, prompt))
    assert out.shape == (b, n)
    np.testing.assert_array_equal(out, np.stack(ref, 1))


def test_tp_decode_matches_single_device():
    """Tensor-parallel decode on a (data=2, model=2) mesh == 1 device."""
    cfg = _cfg(n_heads=4)
    b, p, n = 4, 4, 4
    params = _params(cfg, b, p)
    prompt = jnp.asarray(np.random.default_rng(3).integers(0, 32, (b, p)))

    single = make_lm_generator(
        cfg, prompt_len=p, max_new=n, batch=b, devices=jax.devices()[:1]
    )
    tp = make_lm_generator(
        cfg,
        LMMeshSpec(data=2, model=2),
        prompt_len=p,
        max_new=n,
        batch=b,
        devices=jax.devices()[:4],
    )
    np.testing.assert_array_equal(
        np.asarray(single(params, prompt)), np.asarray(tp(params, prompt))
    )


def test_seq_sharded_decode_matches_single_device():
    """Context-parallel decode: the KV cache shards over the ``seq`` mesh
    axis (the same logical-axis rules as training), and GSPMD inserts the
    gather/reduce for the softmax over the sharded cache — long-prompt
    serving where one device cannot hold the cache.  Token-exact vs one
    device, composed with data and model parallelism."""
    cfg = _cfg(n_heads=4)
    b, p, n = 2, 16, 6
    params = _params(cfg, b, p)
    prompt = jnp.asarray(np.random.default_rng(5).integers(0, 32, (b, p)))

    single = make_lm_generator(
        cfg, prompt_len=p, max_new=n, batch=b, devices=jax.devices()[:1]
    )
    sp = make_lm_generator(
        cfg,
        LMMeshSpec(data=2, seq=2, model=2),
        prompt_len=p,
        max_new=n,
        batch=b,
    )
    np.testing.assert_array_equal(
        np.asarray(single(params, prompt)), np.asarray(sp(params, prompt))
    )


def test_sampled_generation_and_moe():
    """Temperature sampling is deterministic under a fixed key; MoE decode
    runs end-to-end (capacity-based routing makes incremental MoE logits
    legitimately diverge from teacher forcing, so only self-consistency is
    asserted)."""
    cfg = _cfg(num_experts=4, expert_top_k=2)
    b, p, n = 2, 4, 4
    params = _params(cfg, b, p)
    prompt = jnp.asarray(np.random.default_rng(4).integers(0, 32, (b, p)))

    gen = make_lm_generator(
        cfg, prompt_len=p, max_new=n, batch=b, temperature=0.8,
        devices=jax.devices()[:1],
    )
    a = np.asarray(gen(params, prompt, jax.random.key(7)))
    bb = np.asarray(gen(params, prompt, jax.random.key(7)))
    np.testing.assert_array_equal(a, bb)
    assert a.shape == (b, n)
    assert ((a >= 0) & (a < 32)).all()
    # different keys must eventually diverge (an untrained model's output
    # distribution is near-uniform over 32 tokens)
    others = [np.asarray(gen(params, prompt, jax.random.key(s)))
              for s in (8, 9, 10)]
    assert any(not np.array_equal(a, o) for o in others)


def test_top_k_sampling_restricts_support():
    """top_k=1 sampling == greedy decoding, for any temperature."""
    cfg = _cfg()
    b, p, n = 2, 4, 5
    params = _params(cfg, b, p)
    prompt = jnp.asarray(np.random.default_rng(5).integers(0, 32, (b, p)))
    greedy = make_lm_generator(
        cfg, prompt_len=p, max_new=n, batch=b, devices=jax.devices()[:1]
    )
    k1 = make_lm_generator(
        cfg, prompt_len=p, max_new=n, batch=b, temperature=1.3, top_k=1,
        devices=jax.devices()[:1],
    )
    np.testing.assert_array_equal(
        np.asarray(greedy(params, prompt)),
        np.asarray(k1(params, prompt, jax.random.key(3))),
    )


def test_gqa_incremental_matches_full_forward():
    """Grouped-query attention (n_kv_heads < n_heads): the reduced-head KV
    cache and grouped dense_attention reproduce the training forward's
    logits token by token."""
    cfg = _cfg(n_heads=4, n_kv_heads=2)
    b, t = 2, 6
    params = _params(cfg, b, t)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 32, (b, t)))
    ref_logits, _ = TransformerLM(cfg, None).apply({"params": params}, toks)

    caches = init_kv_cache(cfg, b, t)
    assert caches[0].kv[0].shape == (b, t, 2 * 8)  # Hkv=2, half the MHA cache (fused Hkv*Dh storage)
    dec = LMDecode(cfg)
    for i in range(t):
        logits, caches = dec.apply(
            {"params": params}, toks[:, i : i + 1], caches
        )
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(ref_logits[:, i]), atol=1e-5
        )


def test_decode_bench_smoke(capsys):
    """bench/decode.py runs end to end and reports the sweep fields (the
    real-chip numbers live in PERF.md; this guards the harness)."""
    import json
    import sys

    from ddl_tpu.bench import decode as bench_decode

    argv = sys.argv
    sys.argv = [
        "decode", "--batch", "1", "--prompt", "16", "--new", "4",
        "--d-model", "64", "--layers", "2", "--vocab", "64",
        "--kv-heads", "0", "--attn-window", "8", "--iters", "1",
    ]
    try:
        bench_decode.main()
    finally:
        sys.argv = argv
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # CPU walls are microseconds, so the two-length slope can come out
    # negative from noise; the bench then deterministically falls back to
    # the undifferenced quote (flagged `slope_fallback`) instead of
    # raising — the PR-6 "host contention" tier-1 flake.  Real timing
    # signs belong to the real-chip runs (PERF.md).
    assert row["decode_tok_per_sec"] > 0 and row["prefill_ms"] > 0
    # the windowed ring allocates O(window); its per-step read spans the
    # same window rows
    assert row["cache_bytes_per_layer"] < row["max_len"] * 2 * 64 * 4
    assert row["read_bytes_per_step_layer"] <= row["cache_bytes_per_layer"]


def test_rolling_cache_matches_linear_and_is_o_window():
    """The ring cache (rolling=True, O(window) allocation) decodes the
    exact same tokens as the linear cache, for prompts longer and shorter
    than the window, and really allocates only window rows."""
    import flax.linen as nn

    from ddl_tpu.infer.decode import init_kv_cache, make_lm_generator
    from ddl_tpu.models.transformer import LMConfig, TransformerLM

    cfg = LMConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
        d_ff=64, compute_dtype="float32", remat=False, attn_window=6,
    )
    params = nn.meta.unbox(
        TransformerLM(cfg, None).init(
            jax.random.key(0), jnp.zeros((1, 4), jnp.int32)
        )["params"]
    )
    caches = init_kv_cache(cfg, 2, 64, rolling=True)
    assert caches[0].kv[0].shape == (2, 6, 4 * 8)  # (B, window, Hkv*Dh fused)
    rng = np.random.default_rng(0)
    for prompt_len, max_new in ((12, 10), (3, 15)):
        prompt = jnp.asarray(
            rng.integers(0, 64, (1, prompt_len)), jnp.int32
        )
        lin = make_lm_generator(
            cfg, prompt_len=prompt_len, max_new=max_new, rolling=False
        )
        rol = make_lm_generator(
            cfg, prompt_len=prompt_len, max_new=max_new, rolling=True
        )
        np.testing.assert_array_equal(
            np.asarray(lin(params, prompt)), np.asarray(rol(params, prompt))
        )

    # auto mode turns the ring on exactly when a window is set and smaller
    # than the cache; without a window it must reject rolling=True

    with pytest.raises(ValueError, match="attn_window"):
        make_lm_generator(
            dataclasses_replace_no_window(cfg), prompt_len=4, max_new=4,
            rolling=True,
        )


def dataclasses_replace_no_window(cfg):
    import dataclasses

    return dataclasses.replace(cfg, attn_window=0)


def test_flash_prefill_matches_dense_prefill():
    """Flash-kernel prefill (cfg.flash=True routes the prompt pass through
    the Pallas kernel; decode steps stay cached-dense) produces the same
    tokens as the dense prefill, for full-cache and windowed configs."""
    for kw in ({}, {"attn_window": 4}):
        cfg = _cfg(**kw)
        b, p, n = 2, 8, 5
        params = _params(cfg, b, p)
        prompt = jnp.asarray(
            np.random.default_rng(7).integers(0, 32, (b, p))
        )
        dense = make_lm_generator(
            cfg, prompt_len=p, max_new=n, batch=b,
            devices=jax.devices()[:1],
        )
        import dataclasses

        fcfg = dataclasses.replace(cfg, flash=True)
        flash = make_lm_generator(
            fcfg, prompt_len=p, max_new=n, batch=b,
            devices=jax.devices()[:1],
        )
        np.testing.assert_array_equal(
            np.asarray(dense(params, prompt)),
            np.asarray(flash(params, prompt)),
        )
