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
        "calls": 1, "total": tiles * (n // 512), "floor": 8 * (n // 512)}}
    text = compile_for_chip(
        jax.grad(lambda x, w, *t: _sum_f32(product(x, w, *t)), argnums=(0, 1)), *args
    )
    got = kernel_tiles(text)
    assert got["moe_gmm_dx"] == {"calls": 1, "total": tiles * (k // 512), "floor": 8 * (k // 512)}
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
    assert fwd == {"moe_gmm_fwd": {"calls": 1, "total": tiles * bn, "floor": 16 * bn, **col(n)}}
    text = compile_for_chip(
        jax.grad(lambda x, w, *t: _sum_f32(product(x, w, *t)), argnums=(0, 1)), *args
    )
    got = kernel_tiles(text)
    assert got["moe_gmm_dx"] == {"calls": 1, "total": tiles * bk, "floor": 16 * bk, **col(k)}
    assert got["moe_gmm_dw"] == {"calls": 1, "total": tiles * bk * bn, "floor": 16 * bk * bn,
                                 **col(k), **col(n)}


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
