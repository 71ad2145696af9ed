"""Every Pallas kernel compiles for the chip, at the widths it serves.

Interpret mode (the rest of the suite) cannot see what Mosaic refuses:
a block that breaks the (8, 128) tiling rule, a cast or reshape with no
lowering, a working set past the scoped-VMEM limit.  The TPU compiler is
installed beside the CPU backend and compiles for a chip that is
described, not attached, so these cases ask it — shapes only, nothing
runs, no chip time.  This is the only file that describes a topology
(one process may hold libtpu; see the fixtures).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF16 = jnp.bfloat16
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # whatever the missing compiler raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: the next run would warn and
    compile again.  Keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for_chip(one_chip, no_persistent_cache):
    def compile_(fn, *shapes):
        args = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            shapes,
        )
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return text

    return compile_


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _sum_f32(x):
    return x.astype(F32).sum()


@pytest.mark.parametrize(
    "batch,heads,kv_heads,head_dim,seq,window",
    [
        pytest.param(8, 12, 12, 64, 1024, 0, id="gpt2s-mha"),
        pytest.param(8, 12, 4, 64, 1024, 0, id="gpt2s-gqa4"),
        pytest.param(1, 12, 12, 64, 8192, 1024, id="t8192-window1024"),
        # the Trinity-Mini cell's two kinds of layer: 8 query heads a K/V
        # head, whose dK and dV stay in VMEM over 4 K blocks (4 MiB)
        pytest.param(2, 32, 4, 128, 4096, 2048, id="trinity-sliding"),
        pytest.param(2, 32, 4, 128, 4096, 0, id="trinity-full"),
    ],
)
def test_flash_attention_fwd_and_grad(
    compile_for_chip, batch, heads, kv_heads, head_dim, seq, window
):
    from ddl_tpu.ops.flash_attention import flash_attention

    def attend(q, k, v):
        return flash_attention(
            q, k, v, causal=True, window=window, interpret=False
        )

    q = _s((batch, seq, heads, head_dim), BF16)
    kv = _s((batch, seq, kv_heads, head_dim), BF16)
    compile_for_chip(attend, q, kv, kv)
    text = compile_for_chip(
        jax.grad(lambda q, k, v: _sum_f32(attend(q, k, v)), argnums=(0, 1, 2)),
        q, kv, kv,
    )
    # the compiled kernels say what they walk (what ``hbm_plan`` records):
    # the pure plan, over the call's batch x heads rows; the gradients are
    # one kernel's, named for its grid (the K/V head's)
    from ddl_tpu.obs.scope import kernel_tiles
    from ddl_tpu.ops.flash_attention import flash_tile_plan

    plan = flash_tile_plan(seq, causal=True, window=window)
    assert kernel_tiles(text) == {
        name: {"calls": 1, **{k: batch * heads * n for k, n in plan[name].items()}}
        for name in ("flash_bwd_dkv", "flash_fwd")
    }
    assert plan["flash_fwd"]["computed"] < plan["flash_fwd"]["total"]
    # among the counts held above, the (row, K step) pairs of each walk
    assert all(plan[name]["row_steps"] > 0 for name in ("flash_bwd_dkv", "flash_fwd"))


@pytest.mark.parametrize("window", [512, 0], ids=["phi4flash-sliding", "phi4flash-full-and-cross"])
def test_flash_attention_with_a_wider_v_head(compile_for_chip, window):
    """The SambaY cell's differential attention: a layer's 40 query heads
    of 64 on 20 K heads of 64, V = [v1, v2] 128 wide under each K head;
    the output and dV take V's width."""
    from ddl_tpu.obs.scope import kernel_tiles
    from ddl_tpu.ops.flash_attention import flash_attention

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window, interpret=False)

    q, k, v = _s((2, 4096, 40, 64), BF16), _s((2, 4096, 20, 64), BF16), _s((2, 4096, 20, 128), BF16)
    assert jax.eval_shape(attend, q, k, v).shape == (2, 4096, 40, 128)
    compile_for_chip(attend, q, k, v)
    text = compile_for_chip(
        jax.grad(lambda q, k, v: _sum_f32(attend(q, k, v)), argnums=(0, 1, 2)), q, k, v)
    assert set(kernel_tiles(text)) == {"flash_bwd_dkv", "flash_fwd"}
    from ddl_tpu.ops.flash_attention import flash_tile_plan

    plan = flash_tile_plan(4096, causal=True, window=window)
    assert plan["flash_fwd"]["row_steps"] == (5632 if window else 10240)
    for name in ("flash_bwd_dkv", "flash_fwd"):
        assert kernel_tiles(text)[name]["row_steps"] == 2 * 40 * plan[name]["row_steps"]


def test_flash_attention_with_a_narrower_v_head(compile_for_chip):
    """The Kanana-2 cell's latent attention: 32 query heads on as many
    K/V heads, Q and K 192 wide (1.5 lane tiles), V 128; the output and dV
    take V's width.  The backward keeps a head's dK and dV resident, 4096
    x (256 + 128) lanes x 8 bytes = 12.6 MB of VMEM, under its limit."""
    from ddl_tpu.obs.scope import kernel_tiles
    from ddl_tpu.ops.flash_attention import flash_attention, flash_tile_plan

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    qk, v = _s((2, 4096, 32, 192), BF16), _s((2, 4096, 32, 128), BF16)
    assert jax.eval_shape(attend, qk, qk, v).shape == (2, 4096, 32, 128)
    compile_for_chip(attend, qk, qk, v)
    text = compile_for_chip(
        jax.grad(lambda q, k, v: _sum_f32(attend(q, k, v)), argnums=(0, 1, 2)), qk, qk, v)
    plan = flash_tile_plan(4096, causal=True)
    assert kernel_tiles(text) == {
        name: {"calls": 1, **{k: 2 * 32 * n for k, n in plan[name].items()}}
        for name in ("flash_bwd_dkv", "flash_fwd")
    }


# (B, T, H, Hkv, D, Dv, window) of every flash call the benchmark's other LM
# cells make, and the sha256 (first 16 hex digits) of each kernel's custom
# call in the lowered gradient program, forward then backward, with no
# source locations in it: taken at the commit before latent attention, so
# those cells run the kernels they ran
FLASH_PINS = {
    "gpt2s": ((16, 1024, 12, 12, 64, 64, 0), ("bd20659f1635997a", "e7a45fbb992da802")),
    "trinity-sliding": ((2, 4096, 32, 4, 128, 128, 2048),
                        ("0fa27745bca6ecf3", "6edeedcd2a3a288d")),
    "trinity-mellum-full": ((2, 4096, 32, 4, 128, 128, 0),
                            ("f504e98e3c5dd97b", "cd41c7d7c8d8893d")),
    "mellum-sliding": ((2, 4096, 32, 4, 128, 128, 1024),
                       ("232c7a5401aef518", "204aa48323cf14f3")),
    "phi4-sliding": ((2, 4096, 40, 20, 64, 128, 512), ("8d14f490146cbf40", "de3f894b611b6f64")),
    "phi4-full": ((2, 4096, 40, 20, 64, 128, 0), ("99f81f1a21b54215", "7085bbc578bd86eb")),
    "lfm2-full": ((2, 4096, 32, 8, 64, 64, 0), ("2401aefffa544332", "de6b1a26dae3c795")),
}


def test_the_other_cells_flash_kernels_lower_as_before(one_chip, no_persistent_cache):
    import hashlib
    import re

    from ddl_tpu.ops.flash_attention import flash_attention

    def s(shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)

    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.clear_caches()  # no kernel lowered with its source locations is reused
    try:
        got = {}
        for name, ((b, t, h, hkv, d, dv, w), _) in FLASH_PINS.items():
            def attend(q, k, v, w=w):
                return flash_attention(q, k, v, causal=True, window=w, interpret=False)

            text = jax.jit(jax.grad(lambda q, k, v: _sum_f32(attend(q, k, v)),
                                    argnums=(0, 1, 2))).lower(
                s((b, t, h, d)), s((b, t, hkv, d)), s((b, t, hkv, dv))).as_text()
            got[name] = tuple(hashlib.sha256(c.encode()).hexdigest()[:16]
                              for c in re.findall(r"tpu_custom_call\(.*", text))
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
        jax.clear_caches()
    assert got == {name: pins for name, (_, pins) in FLASH_PINS.items()}


def test_selective_scan_fwd_and_grad_at_the_cell_s_widths(compile_for_chip):
    """The SambaY cell's Mamba layer: B=2, T=4096, d_inner 5120, state 16.
    Both kernels compile (128 unrolled steps a chunk; the backward's
    recomputed states are 8 MiB of VMEM at 1024 channels a block), and
    each says its grid and the time steps it walks."""
    from ddl_tpu.obs.scope import kernel_tiles
    from ddl_tpu.ops.selective_scan import BLOCK_D, CHUNK, selective_scan

    b, t, d_in, n = 2, 4096, 5120, 16
    args = (_s((b, t, d_in), F32), _s((b, t, d_in), F32), _s((d_in, n), F32),
            _s((b, t, n), F32), _s((b, t, n), F32), _s((d_in,), F32))

    def scan(*a):
        return selective_scan(*a, interpret=False)[0]

    compile_for_chip(scan, *args)
    text = compile_for_chip(
        jax.grad(lambda *a: scan(*a).sum(), argnums=tuple(range(6))), *args)
    steps = b * (d_in // BLOCK_D) * (t // CHUNK)
    assert kernel_tiles(text) == {
        name: {"calls": 1, "total": steps, "steps": steps * CHUNK}
        for name in ("ssm_scan_bwd", "ssm_scan_fwd")
    }


@pytest.mark.parametrize(
    "k,n", [pytest.param(2048, 1024, id="gate-up"), pytest.param(1024, 2048, id="down")]
)
def test_grouped_matmul_fwd_and_both_grads(compile_for_chip, k, n):
    """The dropless expert layer's products at the Trinity-Mini cell's
    widths: 8 held experts, the buffer of 8192 x 8 choices' worst case."""
    from ddl_tpu.obs.scope import kernel_tiles
    from ddl_tpu.ops.grouped_matmul import ROW_TILE, buffer_rows, grouped_matmul

    rows = buffer_rows(8192 * 8, 8)
    tiles = rows // ROW_TILE
    idx = _s((tiles,), jnp.int32)

    def product(x, w, tg, ts, na):
        return grouped_matmul(x, w, tg, ts, na, interpret=False)

    args = (_s((rows, k), BF16), _s((8, k, n), F32), idx, idx, _s((1,), jnp.int32))
    fwd = kernel_tiles(compile_for_chip(product, *args))
    assert fwd == {"moe_gmm_fwd": {
        "calls": 1, "total": tiles * (n // 512), "floor": 8 * (n // 512), "products": 1}}
    text = compile_for_chip(
        jax.grad(lambda x, w, *t: _sum_f32(product(x, w, *t)), argnums=(0, 1)), *args
    )
    got = kernel_tiles(text)
    assert got["moe_gmm_dx"] == {"calls": 1, "total": tiles * (k // 512), "floor": 8 * (k // 512),
                                 "products": 1}
    assert got["moe_gmm_dw"]["total"] == tiles * (k // 512) * (n // 512)


@pytest.mark.parametrize(
    "k,n", [pytest.param(2304, 896, id="gate-up"), pytest.param(896, 2304, id="down")]
)
def test_grouped_matmul_at_widths_512_does_not_divide(compile_for_chip, k, n):
    """The same products at the Mellum2 cell's widths, 896 = 7 x 128 and
    2304 = 18 x 128, in column blocks of a multiple of 128 lanes: 16 held
    experts, the buffer of 8192 x 8 choices' worst case; each kernel says
    the block it took of each such width."""
    from ddl_tpu.obs.scope import kernel_tiles
    from ddl_tpu.ops.grouped_matmul import ROW_TILE, _col_tile, buffer_rows, grouped_matmul

    rows = buffer_rows(8192 * 8, 16)
    tiles = rows // ROW_TILE
    idx = _s((tiles,), jnp.int32)
    bk, bn = k // _col_tile(k), n // _col_tile(n)
    col = lambda w: {f"col{w}": _col_tile(w)}  # noqa: E731

    def product(x, w, tg, ts, na):
        return grouped_matmul(x, w, tg, ts, na, interpret=False)

    args = (_s((rows, k), BF16), _s((16, k, n), F32), idx, idx, _s((1,), jnp.int32))
    fwd = kernel_tiles(compile_for_chip(product, *args))
    assert fwd == {"moe_gmm_fwd": {"calls": 1, "total": tiles * bn, "floor": 16 * bn,
                                   "products": 1, **col(n)}}
    text = compile_for_chip(
        jax.grad(lambda x, w, *t: _sum_f32(product(x, w, *t)), argnums=(0, 1)), *args
    )
    got = kernel_tiles(text)
    assert got["moe_gmm_dx"] == {"calls": 1, "total": tiles * bk, "floor": 16 * bk,
                                 "products": 1, **col(k)}
    assert got["moe_gmm_dw"] == {"calls": 1, "total": tiles * bk * bn, "floor": 16 * bk * bn,
                                 "products": 1, **col(k), **col(n)}


@pytest.mark.parametrize("d,f,held", [pytest.param(2048, 1024, 8, id="trinity"),
                                      pytest.param(2304, 896, 16, id="mellum2")])
def test_grouped_swiglu_fwd_and_grads(compile_for_chip, d, f, held):
    """The gated pair of the dropless layer at both cells' widths and
    worst-case buffers: one forward kernel and one dx kernel, each two
    MXU products a grid step over both banks' whole blocks (this is the
    VMEM plan the v5e must take), and two weight-gradient calls."""
    from ddl_tpu.obs.scope import kernel_tiles
    from ddl_tpu.ops.grouped_matmul import (
        ROW_TILE, _col_tile, buffer_rows, grouped_swiglu,
    )

    rows = buffer_rows(8192 * 8, held)
    tiles = rows // ROW_TILE
    idx = _s((tiles,), jnp.int32)

    def layer(x, wg, wi, tg, ts, na):
        return grouped_swiglu(x, wg, wi, tg, ts, na, interpret=False)

    bank = _s((held, d, f), F32)
    args = (_s((rows, d), BF16), bank, bank, idx, idx, _s((1,), jnp.int32))
    fwd = {"calls": 1, "total": tiles, "floor": held, "products": 2, f"col{f}": f}
    assert kernel_tiles(compile_for_chip(layer, *args)) == {"moe_gmm_fwd": fwd}
    text = compile_for_chip(
        jax.grad(lambda x, wg, wi, *t: _sum_f32(layer(x, wg, wi, *t)), argnums=(0, 1, 2)),
        *args,
    )
    bd, bf = d // _col_tile(d), f // _col_tile(f)
    col = {f"col{w}": _col_tile(w) for w in (d, f) if w % 512}
    assert kernel_tiles(text) == {
        "moe_gmm_fwd": fwd,
        "moe_gmm_dx": {"calls": 1, "total": tiles, "floor": held, "products": 2, f"col{d}": d},
        "moe_gmm_dw": {"calls": 2, "total": 2 * tiles * bd * bf, "floor": 2 * held * bd * bf,
                       "products": 2, **col},
    }


def test_grouped_products_at_lfm2_s_widths_take_the_pair_s_blocks_whole(compile_for_chip):
    """The LFM2 cell's expert layer: d 2048, F 1792, 8 held experts, the
    buffer of 8192 x 4 choices' worst case.  The gated pair's VMEM
    reckoning fits both banks' whole 2048 x 1792 blocks (about 42 MB in
    the forward, 54 MB in dx, under its 64 MiB limit), so its forward
    takes F in one block of 1792 and its dx K in one block of 2048: each
    row tile read, and its cotangents formed, once.  ``wo``'s products
    and every dw keep ``_col_tile``'s blocks, 896 of 1792 and 512 of
    2048.  Every call within its VMEM limit."""
    from ddl_tpu.obs.scope import kernel_tiles
    from ddl_tpu.ops.grouped_matmul import (
        ROW_TILE, _GATED_VMEM_LIMIT, _gated_vmem, buffer_rows, grouped_matmul,
        grouped_swiglu,
    )

    d, f, held = 2048, 1792, 8
    assert _gated_vmem(f, d, ROW_TILE, 2, dx=False) <= _GATED_VMEM_LIMIT
    assert _gated_vmem(d, f, ROW_TILE, 2, dx=True) <= _GATED_VMEM_LIMIT
    rows = buffer_rows(8192 * 4, held)
    tiles = rows // ROW_TILE
    idx = _s((tiles,), jnp.int32)

    def layer(x, wg, wi, wo, tg, ts, na):
        h = grouped_swiglu(x, wg, wi, tg, ts, na, interpret=False)
        return grouped_matmul(h, wo, tg, ts, na, interpret=False)

    bank = _s((held, d, f), F32)
    args = (_s((rows, d), BF16), bank, bank, _s((held, f, d), F32), idx, idx,
            _s((1,), jnp.int32))
    # the pair's forward in one block of F, wo's in blocks of 512 of 2048
    pair = {"col1792": 1792}
    fwd = {"calls": 2, "total": tiles * (1 + 4), "floor": held * (1 + 4), "products": 2 + 1,
           **pair}
    assert kernel_tiles(compile_for_chip(layer, *args)) == {"moe_gmm_fwd": fwd}
    text = compile_for_chip(
        jax.grad(lambda x, *w: _sum_f32(layer(x, *w)), argnums=(0, 1, 2, 3)), *args)
    assert kernel_tiles(text) == {
        # the sum is linear in wo's product: only the pair's forward is kept
        "moe_gmm_fwd": {"calls": 1, "total": tiles, "floor": held, "products": 2, **pair},
        # the pair's dx in one block of 2048, wo's in blocks of 896 of 1792
        "moe_gmm_dx": {"calls": 2, "total": tiles * (1 + 2), "floor": held * (1 + 2),
                       "products": 2 + 1, "col2048": 2048, "col1792": 896},
        # the pair's two dw (2048 x 1792) and wo's (1792 x 2048): 8 blocks each
        "moe_gmm_dw": {"calls": 3, "total": 3 * tiles * 8, "floor": 3 * held * 8,
                       "products": 3, "col1792": 896},
    }


def test_the_lfm2_cell_s_whole_step_fits_one_chip(topo, no_persistent_cache, monkeypatch):
    """The LFM2 cell's train step at its every width, batch 2 x 4096,
    compiled for one described v5e from shapes alone: the grouped products,
    the row kernels and the flash kernels in it, and its plan (arguments,
    outputs not aliased to them, temporaries) under the chip's
    ``bytes_limit`` (16,909,336,064 bytes), with no remat."""
    import importlib
    import os
    import sys

    import flax.linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import harness
    from benchmark.families.lfm2 import lm_config
    from ddl_tpu.models.transformer import TransformerLM
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import LMTrainState, make_lm_step_fns
    from ddl_tpu.train.state import build_optimizer

    # the kernels' own entry points resolve the backend: here that is the CPU
    for mod in ("flash_attention", "grouped_matmul", "moe_rows"):
        monkeypatch.setattr(importlib.import_module(f"ddl_tpu.ops.{mod}"),
                            "interpret_default", lambda: False)
    spec = harness.load_cell(root, "lfm2-8b.train_b2_t4096")
    cfg, w = lm_config(spec["config"]["model"]), spec["workload"]
    assert not cfg.remat
    o = w["optimizer"]
    tx = build_optimizer(o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"])
    fns = make_lm_step_fns(cfg, LMMeshSpec(), tx, jax.random.key(0), w["batch"], w["seq_len"],
                           devices=topo.devices[:1])
    tok = jnp.zeros((w["batch"], w["seq_len"]), jnp.int32)
    params = nn.meta.unbox(jax.eval_shape(
        lambda: TransformerLM(cfg).init(jax.random.key(0), tok)["params"]))
    state = LMTrainState(step=_s((), jnp.int32), params=params,
                         opt_state=jax.eval_shape(tx.init, params))
    rep = NamedSharding(fns.mesh, P())
    state, tok = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), (state, tok))
    compiled = fns.train.lower(state, tok, tok).compile()
    text = compiled.as_text()
    for kernel in ("moe_gmm_fwd", "moe_gmm_dx", "moe_gmm_dw", "moe_rows_gather", "flash_fwd"):
        assert kernel in text, kernel
    ma = compiled.memory_analysis()
    plan = (ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
            + ma.temp_size_in_bytes)
    # 16 bytes a parameter: weights, Adam's two moments, the gradient's room
    assert ma.argument_size_in_bytes >= 12 * 507_820_288
    assert plan < 16_909_336_064, plan


def test_the_kanana_cell_s_whole_step_fits_one_chip(topo, no_persistent_cache, monkeypatch):
    """The Kanana-2 cell's train step at its every width, batch 2 x 4096,
    compiled for one described v5e from shapes alone: latent attention's
    flash kernels at Q/K 192 over V 128, the grouped products and the row
    kernels in it, and its plan (arguments, outputs not aliased to them,
    temporaries) under the chip's ``bytes_limit`` (16,909,336,064 bytes),
    with no remat."""
    import importlib
    import os
    import sys

    import flax.linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import harness
    from benchmark.families.deepseek_v3 import lm_config
    from ddl_tpu.models.transformer import TransformerLM
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import LMTrainState, make_lm_step_fns
    from ddl_tpu.train.state import build_optimizer

    # the kernels' own entry points resolve the backend: here that is the CPU
    for mod in ("flash_attention", "grouped_matmul", "moe_rows"):
        monkeypatch.setattr(importlib.import_module(f"ddl_tpu.ops.{mod}"),
                            "interpret_default", lambda: False)
    spec = harness.load_cell(root, "kanana2.train_b2_t4096")
    cfg, w = lm_config(spec["config"]["model"]), spec["workload"]
    assert not cfg.remat and cfg.mla
    o = w["optimizer"]
    tx = build_optimizer(o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"])
    fns = make_lm_step_fns(cfg, LMMeshSpec(), tx, jax.random.key(0), w["batch"], w["seq_len"],
                           devices=topo.devices[:1])
    tok = jnp.zeros((w["batch"], w["seq_len"]), jnp.int32)
    params = nn.meta.unbox(jax.eval_shape(
        lambda: TransformerLM(cfg).init(jax.random.key(0), tok)["params"]))
    state = LMTrainState(step=_s((), jnp.int32), params=params,
                         opt_state=jax.eval_shape(tx.init, params))
    rep = NamedSharding(fns.mesh, P())
    state, tok = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), (state, tok))
    compiled = fns.train.lower(state, tok, tok).compile()
    text = compiled.as_text()
    for kernel in ("moe_gmm_fwd", "moe_gmm_dx", "moe_gmm_dw", "moe_rows_gather", "flash_fwd",
                   "flash_bwd_dkv"):
        assert kernel in text, kernel
    ma = compiled.memory_analysis()
    plan = (ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
            + ma.temp_size_in_bytes)
    # 12 bytes a parameter in: weights and Adam's two moments (the
    # gradient's room is among the temporaries)
    assert ma.argument_size_in_bytes >= 12 * 424_961_024
    assert plan < 16_909_336_064, plan


@pytest.mark.parametrize("dtype,passes", [(BF16, 1), (F32, 3)], ids=["bf16", "f32"])
@pytest.mark.parametrize("held,d,shared", [(8, 2048, True), (16, 2304, False)],
                         ids=["trinity", "mellum2"])
def test_moe_row_kernels_fwd_and_grads(compile_for_chip, monkeypatch, held, d, shared, dtype, passes):
    """The dropless shuffle's four row kernels at the Trinity-Mini cell's
    shapes (a 67,584 x 2,048 buffer, 8 held experts, the shared experts'
    output added in the combine) and at the Mellum2 cell's (69,632 x
    2,304, 16 held, none): 8,192 tokens, top-8; what each says of its grid
    in ``kernel_tiles``, and of its MXU products a (row tile, token tile)
    pair: one where the operands are bf16, the cotangents among them (the
    combine leaves in the compute type), three for a float32's addends."""
    from ddl_tpu.models.transformer import _rows_combine, _rows_gather, dropless_plan
    from ddl_tpu.obs.scope import kernel_tiles
    from ddl_tpu.ops import moe_rows
    from ddl_tpu.ops.grouped_matmul import ROW_TILE, buffer_rows
    from ddl_tpu.ops.moe_rows import pairs_bound

    # the layer's own entry points resolve the backend: here that is the CPU
    monkeypatch.setattr(moe_rows, "interpret_default", lambda: False)

    tokens, k = 8192, 8
    rows = buffer_rows(tokens * k, held)
    assert rows == {8: 67584, 16: 69632}[held]
    token_tiles = tokens // ROW_TILE
    pairs = pairs_bound(rows // ROW_TILE, token_tiles, held)

    def shuffle(x, w, idx):
        plan = dropless_plan(idx, 0, held, ROW_TILE)
        return _rows_combine(_rows_gather(x, plan), w, plan, x if shared else None)

    args = (_s((tokens, d), dtype), _s((tokens, k), F32), _s((tokens, k), jnp.int32))
    row_side = {"calls": 1, "total": pairs, "floor": held, "passes": passes}
    token_side = {"calls": 1, "total": pairs + token_tiles, "floor": held + token_tiles,
                  "passes": passes}
    assert kernel_tiles(compile_for_chip(shuffle, *args)) == {
        "moe_rows_combine": token_side, "moe_rows_gather": row_side}
    text = compile_for_chip(
        # quadratic, so that the gradient needs the combine's value too
        jax.grad(lambda x, w, idx: _sum_f32(shuffle(x, w, idx).astype(F32) ** 2), argnums=(0, 1)),
        *args,
    )
    assert kernel_tiles(text) == {
        "moe_rows_combine": token_side, "moe_rows_combine_bwd": row_side,
        "moe_rows_gather": row_side, "moe_rows_gather_bwd": token_side}


@pytest.mark.parametrize("kv_heads", [12, 4], ids=["fused768", "fused256"])
@pytest.mark.parametrize("cache_len", [1024, 8192])
def test_decode_attention_bf16_and_int8(compile_for_chip, cache_len, kv_heads):
    """Both entry points, with the per-lane (B, L) bias the serving
    engine's continuous batch passes."""
    from ddl_tpu.ops.decode_attention import (
        decode_attention,
        quant_decode_attention,
    )

    b, fused = 32, kv_heads * 64
    q = _s((b, 1, 12, 64), BF16)
    bias = _s((b, cache_len), F32)
    cache = _s((b, cache_len, fused), BF16)
    compile_for_chip(
        lambda q, ck, cv, bias: decode_attention(
            q, ck, cv, bias, hkv=kv_heads, interpret=False
        ),
        q, cache, cache, bias,
    )
    cache8 = _s((b, cache_len, fused), jnp.int8)
    scales = _s((b, kv_heads, cache_len), F32)
    compile_for_chip(
        lambda q, ck, ks, cv, vs, bias: quant_decode_attention(
            q, ck, ks, cv, vs, bias, hkv=kv_heads, interpret=False
        ),
        q, cache8, scales, cache8, scales, bias,
    )


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize(
    "hw,c0,layers",
    [
        pytest.param(56, 64, 6, id="densenet121-block1"),
        pytest.param(7, 512, 16, id="densenet121-block4"),
    ],
)
def test_fused_dense_block(compile_for_chip, hw, c0, layers, grad):
    from ddl_tpu.ops.fused_dense_block import block_pad, fused_dense_block

    growth, bn = 32, 128
    _, p_total = block_pad(c0, layers, growth)
    x0 = _s((30, hw, hw, c0), BF16)
    packed = {
        "a1": _s((layers, 1, p_total), F32),
        "b1": _s((layers, 1, p_total), F32),
        "w1": _s((layers, p_total, bn), F32),
        "a2": _s((layers, 1, bn), F32),
        "b2": _s((layers, 1, bn), F32),
        "w2": _s((layers, 9, bn, growth), F32),
    }

    def block(x0, packed):
        return fused_dense_block(
            x0, packed, c0=c0, growth=growth, interpret=False
        )

    if grad:
        compile_for_chip(
            jax.grad(lambda x0, p: _sum_f32(block(x0, p)), argnums=(0, 1)),
            x0, packed,
        )
    else:
        compile_for_chip(block, x0, packed)


@pytest.mark.parametrize("out_features", [3072, 50304], ids=["mlp", "lm-head"])
def test_int8_matvec(compile_for_chip, out_features):
    from ddl_tpu.ops.int8_matvec import int8_matmul_small_m

    compile_for_chip(
        lambda x, w8, scale: int8_matmul_small_m(
            x, w8, scale, interpret=False
        ),
        _s((1, 768), BF16),
        _s((768, out_features), jnp.int8),
        _s((out_features,), F32),
    )


def test_pallas_normalize_images(compile_for_chip):
    from ddl_tpu.ops.pallas_image import pallas_normalize_images

    compile_for_chip(
        lambda images: pallas_normalize_images(images, BF16, interpret=False),
        _s((30, 224, 224, 3), jnp.uint8),
    )
