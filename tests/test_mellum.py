"""The mellum block (ISSUE 33) at a small size on the CPU: the program's
``TransformerLM`` built from ``LMConfig``'s two-statement router (softmax
scores through the dropless layer) and its rotary by kind of layer (plain
in the sliding layers, YaRN in the full one) against the plain reference
``benchmark/reference/mellum.py`` on seeded weights; the YaRN table
against the closed form; the share arithmetic; the grouped product and
the gated pair (``grouped_swiglu``) at the published widths 896 and 2304.

Sizes: d 64, 4 x 16 heads, 2 K/V heads, 8 experts top-2, no shared
expert, window 8, T 32, layers sliding, sliding, sliding, full; YaRN
factor 4 over 16 original positions.
"""

import dataclasses
import math
import os
import sys

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families.common import flatten, unflatten_like  # noqa: E402
from benchmark.families.mellum import lm_config  # noqa: E402
from benchmark.reference import common as refcommon  # noqa: E402
from benchmark.reference import mellum as ref  # noqa: E402
from ddl_tpu.models import transformer  # noqa: E402
from ddl_tpu.models.transformer import LMConfig, MoeMlp, Rope, TransformerLM  # noqa: E402
from ddl_tpu.ops import grouped_matmul as gm  # noqa: E402

F32 = refcommon.caster("f32")

PUBLISHED_YARN = dict(theta=500000, factor=16, original=8192, beta_fast=32, beta_slow=1,
                      attention_factor=1.2772588722239782)


def small_model(held=8, share=0, **over):
    m = dict(
        vocab_size=96, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16,
        moe_d_ff=32, num_experts=8, experts_held=held, expert_share_index=share,
        expert_top_k=2, layer_types=["sliding_attention"] * 3 + ["full_attention"],
        sliding_window=8,
        rope={"sliding_attention": {"theta": 10000},
              "full_attention": {"theta": 10000, "factor": 4, "original": 16, "beta_fast": 2,
                                 "beta_slow": 0.5, "attention_factor": 1.1386}},
        norm_eps=1e-6, compute_dtype="float32", flash=False, remat=False,
    )
    m.update(over)
    return m


def program_and_weights(model, seed=3, t=32):
    cfg = lm_config(model)
    lm = TransformerLM(cfg)
    tok = jax.random.randint(jax.random.key(seed + 1), (2, t + 1), 0, model["vocab_size"])
    inp, tgt = tok[:, :-1], tok[:, 1:]
    template = flax.core.meta.unbox(
        jax.eval_shape(lambda: lm.init(jax.random.key(0), inp))["params"])
    flat = ref.init_params(jax.random.key(seed), model)
    return lm, flat, unflatten_like(template, flat), inp, tgt


# Both sides compute in float32 on the CPU at the highest precision; what
# is left is the order of float32 sums (1e-6 relative on a leaf's largest
# entry).  1e-4 leaves two orders of room and is orders under what a wrong
# weight, mask, norm or rotary table reads.
RTOL = 1e-4


@pytest.fixture(scope="module")
def both_sides():
    """Logits, loss and gradients of program and reference, once: a
    sliding and a full layer, the second share of four (experts 2 and 3
    of 8 held; the shares' sum is the share test's)."""
    model = small_model(2, 1, n_layers=2, layer_types=["sliding_attention", "full_attention"])
    lm, flat, params, inp, tgt = program_and_weights(model)

    def loss_of(p):
        logits, _ = lm.apply({"params": p}, inp)
        lse = jax.scipy.special.logsumexp(logits, -1)
        return (lse - jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]).mean(), logits

    # jitted: the same numbers as op by op, a quarter of the time
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(loss_of, has_aux=True))(params)
    ref_loss, ref_grads = jax.jit(ref.make_grad_fn(model, F32))(flat, (inp, tgt))
    ref_logits = jax.jit(lambda p: ref.forward_logits(p, inp, model, F32))(flat)
    return dict(logits=logits, loss=loss, grads=flatten(grads), ref_logits=ref_logits,
                ref_loss=ref_loss, ref_grads=ref_grads)


def test_program_logits_agree_with_the_reference(both_sides):
    s = both_sides
    scale = float(jnp.abs(s["ref_logits"]).max())
    assert float(jnp.abs(s["logits"] - s["ref_logits"]).max()) <= RTOL * scale


def test_program_loss_agrees_with_the_reference(both_sides):
    s = both_sides
    assert abs(float(s["loss"]) - float(s["ref_loss"])) <= RTOL * abs(float(s["ref_loss"]))


def test_program_gradients_agree_with_the_reference_leaf_by_leaf(both_sides):
    got, want = both_sides["grads"], both_sides["ref_grads"]
    assert set(got) == set(want)
    for name, w in want.items():
        top = float(jnp.abs(w).max())
        assert top > 0.0, name
        assert float(jnp.abs(got[name] - w).max()) <= RTOL * top, name


# ------------------------------------------------------------ the rotaries


def test_the_published_yarn_table_against_the_closed_form():
    """low 18, high 35 at head_dim 128; below the ramp the plain
    frequencies, above it a sixteenth of them, a blend between."""
    rope = Rope(**PUBLISHED_YARN)
    assert rope.ramp_ends(128) == (18, 35)
    got = np.asarray(rope.inv_freq(64), np.float64)
    i = np.arange(64)
    ext = 500000.0 ** (-2 * i / 128)
    r = np.clip((i - 18) / (35 - 18), 0, 1)
    want = ext / 16 * r + ext * (1 - r)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(got[:19], ext[:19], rtol=2e-6)
    np.testing.assert_allclose(got[35:], ext[35:] / 16, rtol=2e-6)
    # the reference computes its own, from the file's entry
    theirs, factor = ref.inv_freq(dict(PUBLISHED_YARN), 128)
    np.testing.assert_allclose(np.asarray(theirs, np.float64), want, rtol=2e-6)
    assert factor == PUBLISHED_YARN["attention_factor"]
    # the published factor is transformers' 0.1 ln(factor) + 1
    assert math.isclose(0.1 * math.log(16) + 1, PUBLISHED_YARN["attention_factor"], rel_tol=1e-12)


def test_the_attention_factor_scales_cos_and_sin():
    x = jax.random.normal(jax.random.key(0), (1, 8, 2, 16))
    scaled = transformer._rope(x, Rope(10000.0, 4.0, 16, 2.0, 0.5, 1.25))
    plain = transformer._rope(x, Rope(10000.0, 4.0, 16, 2.0, 0.5, 1.0))
    np.testing.assert_allclose(scaled, 1.25 * plain, rtol=1e-6)
    # position 0 turns nothing: what is left is the factor
    np.testing.assert_allclose(scaled[:, 0], 1.25 * x[:, 0], rtol=1e-6)


def test_yarn_at_factor_1_is_the_plain_rotary():
    x = jax.random.normal(jax.random.key(1), (2, 12, 2, 16))
    plain = transformer._rope(x, Rope(10000.0))
    same = transformer._rope(x, Rope(10000.0, 1.0, 16, 2.0, 0.5))
    np.testing.assert_array_equal(plain, same)
    half = 8
    np.testing.assert_allclose(
        Rope(10000.0).inv_freq(half), 10000.0 ** (-np.arange(half) / half), rtol=1e-6)


def test_rotary_is_said_by_kind_of_layer():
    cfg = lm_config(small_model())
    assert [cfg.layer_rope(i).factor for i in range(4)] == [1.0, 1.0, 1.0, 4.0]
    # a kind not named keeps the old rule; a kind named None rotates nothing
    old = LMConfig(n_layers=2, layer_types=("sliding_attention", "full_attention"), attn_window=8)
    assert old.layer_rope(0) == Rope(old.rope_theta) and old.layer_rope(1) is None
    none = LMConfig(rope_by_kind=(("full_attention", None),))
    assert none.layer_rope(0) is None
    with pytest.raises(ValueError, match="rope_by_kind"):
        LMConfig(rope_by_kind=(("mamba", Rope()),))
    with pytest.raises(ValueError, match="factor >= 1 and the original length"):
        Rope(10000.0, 4.0)


# ------------------------------------------------------- the expert layer


def moe_layer(cfg, x, layer_params):
    y, col = MoeMlp(cfg).apply({"params": layer_params}, x, mutable=["intermediates"])
    return y[0], {k: float(v[0]) for k, v in col["intermediates"].items()}


def layer_weights(model, seed=5):
    one = dict(model, n_layers=1, layer_types=["sliding_attention"])
    flat = ref.init_params(jax.random.key(seed), one)
    p = {k[len("block0/"):]: v for k, v in flat.items() if k.startswith("block0/moe/")}
    x = jax.random.normal(jax.random.key(seed + 1), (2, 32, model["d_model"]), jnp.float32)
    return p, x


def as_tree(p):
    tree = {}
    for k, v in p.items():
        node = tree
        parts = k[len("moe/"):].split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = v
    return tree


def ref_mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """``expert_share`` (0, 4) .. (3, 4), 2 experts each: their outputs
    summed (nothing is computed by every share alike: no shared expert)
    equal the reference's layer with all 8 held."""
    model = small_model()
    p, x = layer_weights(model)
    m = x.reshape(-1, model["d_model"])
    whole = ref.moe_layer(ref_mm, m, p, model)
    total = jnp.zeros_like(whole)
    rows = 0.0
    with jax.default_matmul_precision("highest"):
        for i in range(4):
            part = dict(p, **{k: p[k][2 * i:2 * i + 2] for k in ("moe/wg", "moe/wi", "moe/wo")})
            cfg = lm_config(small_model(2, i))
            assert cfg.expert_share == (i, 4)
            y, counters = moe_layer(cfg, x, as_tree(part))
            assert counters["moe_rows_dropped"] == 0.0
            rows += counters["moe_local_rows"]
            total = total + y.reshape(m.shape)
    assert rows == 2 * 64  # every choice lands on exactly one share
    assert float(jnp.abs(total - whole).max()) <= RTOL * float(jnp.abs(whole).max())


def test_softmax_scores_renormalised_against_a_hand_loop():
    """Token by token in numpy: softmax over the 8 experts, the top 2,
    their probabilities over their sum, each chosen expert's SwiGLU."""
    model = small_model()
    p, x = layer_weights(model, seed=11)
    with jax.default_matmul_precision("highest"):
        y, counters = moe_layer(lm_config(model), x, as_tree(p))
    m = np.asarray(x, np.float64).reshape(-1, 64)
    wr, wg, wi, wo = (np.asarray(p[k], np.float64) for k in
                      ("moe/router/kernel", "moe/wg", "moe/wi", "moe/wo"))
    want, mass = np.zeros_like(m), []
    for n, row in enumerate(m):
        z = row @ wr
        prob = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
        top = np.argsort(-prob)[:2]
        assert prob[top[1]] - np.sort(prob)[-3] > 1e-6  # a tie-free seed
        mass.append(prob[top].sum())
        for e in top:
            g = row @ wg[e]
            want[n] += prob[e] / prob[top].sum() * ((g / (1 + np.exp(-g)) * (row @ wi[e])) @ wo[e])
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape), want,
                               rtol=0, atol=RTOL * np.abs(want).max())
    assert counters["moe_topk_mass"] == pytest.approx(np.mean(mass), rel=1e-5)
    assert 2 / 8 < counters["moe_topk_mass"] < 1.0


def test_softmax_scores_bring_no_bias_leaf_and_sigmoid_scores_keep_theirs():
    cfg = lm_config(small_model())
    x = jnp.zeros((1, 8, 64))
    leaves = flatten(flax.core.meta.unbox(
        jax.eval_shape(lambda: MoeMlp(cfg).init(jax.random.key(0), x))["params"]))
    assert sorted(leaves) == ["router/kernel", "wg", "wi", "wo"]
    sig = LMConfig(d_model=64, num_experts=8, moe_router="sigmoid", moe_d_ff=32,
                   mlp_gated=True, compute_dtype="float32")
    assert sig.moe_dropless
    leaves = flatten(flax.core.meta.unbox(
        jax.eval_shape(lambda: MoeMlp(sig).init(jax.random.key(0), x))["params"]))
    assert "bias" in leaves


def test_score_and_dispatch_are_two_statements():
    assert not LMConfig(num_experts=8).moe_dropless          # softmax, capacity
    assert LMConfig(num_experts=8, moe_layer="dropless").moe_dropless
    assert LMConfig(num_experts=8, moe_router="sigmoid").moe_dropless
    with pytest.raises(ValueError, match="sigmoid scores under a token capacity"):
        LMConfig(num_experts=8, moe_router="sigmoid", moe_layer="capacity")
    # what the capacity layer would ignore is refused, not ignored
    for ignored in (dict(expert_share=(0, 2)), dict(num_shared_experts=1), dict(route_scale=2.0)):
        with pytest.raises(ValueError, match="are the dropless layer's"):
            LMConfig(num_experts=8, **ignored)
    with pytest.raises(ValueError, match="moe_layer must be"):
        LMConfig(num_experts=8, moe_layer="dropful")


def test_route_scale_is_applied_under_softmax_scores():
    model = small_model()
    p, x = layer_weights(model)
    cfg = lm_config(model)
    with jax.default_matmul_precision("highest"):
        one, _ = moe_layer(cfg, x, as_tree(p))
        two, _ = moe_layer(dataclasses.replace(cfg, route_scale=2.0), x, as_tree(p))
    np.testing.assert_allclose(two, 2.0 * one, rtol=1e-5, atol=1e-7)


def test_the_pipeline_and_the_serving_engine_refuse_the_stack_by_their_own_messages():
    from ddl_tpu.parallel.lm_pipeline import make_lm_pipeline_step_fns
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.serve.engine import make_serve_step_fns
    from ddl_tpu.train.state import build_optimizer

    cfg = lm_config(small_model())
    with pytest.raises(NotImplementedError, match="stacks one block"):
        make_lm_pipeline_step_fns(
            cfg, LMMeshSpec(pipe=2), build_optimizer(1e-5), jax.random.key(0), 2, 32, 2)
    with pytest.raises(NotImplementedError, match="mixed sliding and full.*ROADMAP R2"):
        make_serve_step_fns(cfg, block_size=8, num_blocks=8, max_batch=2)


# ------------------------------------------- the grouped product's widths


def _gmm_case(k, n, tile=8, groups=3, counts=(9, 0, 14)):
    counts = jnp.array(counts, jnp.int32)
    rows = gm.buffer_rows(24, groups, tile)
    start, tg, ts, na = gm.align_groups(counts, rows // tile, tile)
    r = jnp.arange(rows)
    g = tg[r // tile]
    valid = ((r - start[g] < counts[g]) & (r // tile < na[0]))[:, None]
    x = jnp.where(valid, jax.random.normal(jax.random.key(0), (rows, k)), 0.0)
    w = jax.random.normal(jax.random.key(1), (groups, k, n)) / math.sqrt(k)
    dy = jnp.where(valid, jax.random.normal(jax.random.key(2), (rows, n)), 0.0)
    return tile, tg, ts, na, valid, x, w, dy


@pytest.fixture(scope="module", params=[(2304, 896), (896, 2304), (2048, 1024), (1024, 2048)],
                ids=["2304x896", "896x2304", "2048x1024", "1024x2048"])
def gmm_sides(request):
    """The grouped product (interpret mode) and the ``jnp`` loop at one
    pair of widths: forward and both gradients, once."""
    k, n = request.param
    tile, tg, ts, na, valid, x, w, dy = _gmm_case(k, n)

    def loss(fn):
        return lambda x, w: jnp.sum(jnp.where(valid, fn(x, w), 0.0) * dy)

    ours = lambda x, w: gm.grouped_matmul(x, w, tg, ts, na, tile=tile)  # noqa: E731
    loop = lambda x, w: gm.grouped_matmul_reference(x, w, tg, na, tile=tile)  # noqa: E731
    out = {"valid": valid, "fwd": (ours(x, w), loop(x, w)), "widths": (k, n)}
    out["dx"], out["dw"] = zip(jax.grad(loss(ours), (0, 1))(x, w), jax.grad(loss(loop), (0, 1))(x, w))
    return out


@pytest.mark.parametrize("which", ["fwd", "dx", "dw"])
def test_grouped_product_at_the_published_widths(gmm_sides, which):
    """Widths 896 and 2304 go through in column blocks of a multiple of
    128 lanes; 1024 and 2048 in the blocks of 512 they had."""
    got, want = gmm_sides[which]
    if which != "dw":
        got, want = (jnp.where(gmm_sides["valid"], a, 0.0) for a in (got, want))
    assert float(jnp.abs(got - want).max()) < 2e-4 * float(jnp.abs(want).max())


def test_column_blocks_by_width():
    assert [gm._col_tile(n) for n in (64, 512, 1024, 2048, 6144)] == [64, 512, 512, 512, 512]
    assert gm._col_tile(896) == 896 and 2304 % gm._col_tile(2304) == 0
    assert gm._col_tile(2304) % 128 == 0 and gm._col_tile(2304) <= gm._COL_TILE_128
    # only a width the 128-lane rule cut says its block in the kernel's metadata
    assert gm._col_metadata(2048, 512) == {} and gm._col_metadata(896, 896) == {"col896": 896}


# Which of the gated pair's block rules a case takes: both banks' whole
# blocks beside a row tile (what both cells' widths get), or ``_col_tile``'s
# blocks, the path of a width too wide for that (a budget of 0 bytes).
SWIGLU_CASES = [
    pytest.param((k, n, blocks, dtype), id=f"{k}x{n}-{blocks}{tag}")
    for k, n, blocks, dtype, tag in [
        (2048, 1024, "whole", jnp.float32, ""), (2048, 1024, "blocks", jnp.float32, ""),
        (2304, 896, "whole", jnp.float32, ""), (2304, 896, "blocks", jnp.float32, ""),
        (2304, 896, "whole", jnp.bfloat16, "-bf16"),
    ]
]


@pytest.fixture(scope="module", params=SWIGLU_CASES)
def swiglu_sides(request):
    """``grouped_swiglu`` (interpret mode), the composition it replaced
    (``_swiglu`` over two ``grouped_matmul``) and the float32 loop
    (``grouped_matmul_reference`` composed): value, and the gradients of
    ``x``, ``wg`` and ``wi``, once a case.  ``_gmm_case``'s counts give a
    group with no rows, a group whose last tile is partly padding, and a
    tile past ``n_active``."""
    k, n, blocks, dtype = request.param
    tile, tg, ts, na, valid, x, wg, dy = _gmm_case(k, n)
    wi = jax.random.normal(jax.random.key(3), wg.shape) / math.sqrt(k)
    x, dy = x.astype(dtype), dy.astype(dtype)
    assert int(na[0]) < tg.shape[0]  # a tile past n_active is there

    def loss(fn):
        return lambda x, wg, wi: jnp.sum(
            jnp.where(valid, fn(x, wg, wi).astype(jnp.float32), 0.0) * dy)

    def fused(x, wg, wi):
        return gm.grouped_swiglu(x, wg, wi, tg, ts, na, tile=tile)

    def composition(x, wg, wi):
        return transformer._swiglu(gm.grouped_matmul(x, wg, tg, ts, na, tile=tile),
                                   gm.grouped_matmul(x, wi, tg, ts, na, tile=tile))

    def loop(x, wg, wi):
        return (jax.nn.silu(gm.grouped_matmul_reference(x, wg, tg, na, tile=tile))
                * gm.grouped_matmul_reference(x, wi, tg, na, tile=tile))

    out = {"valid": valid, "dtype": dtype}
    with pytest.MonkeyPatch.context() as m:
        if blocks == "blocks":
            # no block fits: the narrowest, 128 lanes, so dx walks K in
            # 16 or 18 passes and the forward F in 8 or 7
            m.setattr(gm, "_GATED_VMEM_LIMIT", 0)
        for name, fn in [("fused", fused), ("composition", composition), ("loop", loop)]:
            out[name] = (fn(x, wg, wi), *jax.grad(loss(fn), (0, 1, 2))(x, wg, wi))
    return out


@pytest.mark.parametrize("against", ["composition", "loop"])
@pytest.mark.parametrize("which", ["h", "dx", "dwg", "dwi"])
def test_gated_pair_against_the_composition_and_the_loop(swiglu_sides, which, against):
    """The fused pair's value and gradients against what it replaced and
    against float32: within float32's rounding of the sums in float32,
    within bf16's in bf16 (the fused pair rounds fewer times: the
    activation from the float32 products, dx from one accumulator)."""
    i = ["h", "dx", "dwg", "dwi"].index(which)
    got, want = (swiglu_sides[side][i].astype(jnp.float32) for side in ("fused", against))
    if which in ("h", "dx"):  # rows of inactive tiles are left unwritten
        got, want = (jnp.where(swiglu_sides["valid"], a, 0.0) for a in (got, want))
    tol = 2e-4 if swiglu_sides["dtype"] == jnp.float32 else 2e-2
    assert float(jnp.abs(got - want).max()) < tol * float(jnp.abs(want).max())


@pytest.mark.parametrize("d,f,fwd,dx", [
    pytest.param(2048, 1024, 1024, 2048, id="trinity"),
    pytest.param(2304, 896, 896, 2304, id="mellum2"),
    pytest.param(2048, 1792, 1792, 2048, id="lfm2"),
    pytest.param(4096, 2048, 1024, 2048, id="past-the-limit"),
])
def test_the_gated_pair_takes_the_widest_block_whose_vmem_fits(d, f, fwd, dx):
    """The pair's forward blocks F against the whole of d, its dx blocks d
    against the whole of F: each takes the whole width where its VMEM
    reckoning fits, else the widest multiple of 128 lanes that divides
    it and fits; the next wider candidate does not fit."""
    for n, k, want, is_dx in [(f, d, fwd, False), (d, f, dx, True)]:
        got = gm._gated_tile(n, k, gm.ROW_TILE, 2, dx=is_dx)
        assert got == want
        assert gm._gated_vmem(got, k, gm.ROW_TILE, 2, dx=is_dx) <= gm._GATED_VMEM_LIMIT
        wider = [b for b in range(got + 128, n + 1, 128) if n % b == 0]
        assert all(gm._gated_vmem(b, k, gm.ROW_TILE, 2, dx=is_dx) > gm._GATED_VMEM_LIMIT
                   for b in wider)


def test_a_width_that_128_does_not_divide_is_refused_by_name():
    with pytest.raises(ValueError, match="must be a multiple of 128"):
        gm._col_tile(900)
    tile, tg, ts, na, _, x, w, _ = _gmm_case(64, 900)
    with pytest.raises(ValueError, match="width 900 must be a multiple of 128"):
        gm.grouped_matmul(x, w, tg, ts, na, tile=tile)


# ----------------------------------------------------------- the counters


def test_step_metrics_carry_the_dropless_counters_and_the_topk_mass():
    from ddl_tpu.train.lm_steps import sown_metrics

    model = small_model()
    lm, _, params, inp, _ = program_and_weights(model)
    _, col = jax.jit(lambda p: lm.apply({"params": p}, inp, mutable=["intermediates"]))(params)
    m = {k: float(v) for k, v in sown_metrics(col["intermediates"]).items()}
    assert set(m) == {"moe_local_rows", "moe_load_max_over_mean", "moe_rows_dropped",
                      "moe_buffer_fill", "moe_topk_mass"}
    assert m["moe_local_rows"] == 4 * 2 * 64 and m["moe_rows_dropped"] == 0.0
    assert 2 / 8 < m["moe_topk_mass"] < 1.0


def test_the_period_event_and_summarize_carry_the_topk_mass(tmp_path):
    from ddl_tpu.obs import EventWriter, StepTrace, read_events
    from ddl_tpu.obs.report import load_run, render_summary, summarize_run

    w = EventWriter(tmp_path, "job", host=0)
    trace = StepTrace(w)
    trace.begin_period()
    trace.end_period(0, 5, elapsed=1.0, steps=5, metrics={
        "loss": 9.5, "moe_local_rows": 65536.0, "moe_load_max_over_mean": 1.2,
        "moe_rows_dropped": 0.0, "moe_buffer_fill": 0.3, "moe_topk_mass": 0.31})
    w.close()
    (period,) = [e for e in read_events(w.path) if e["kind"] == "period"]
    assert period["moe_topk_mass"] == 0.31
    text = render_summary(summarize_run(load_run(tmp_path, "job")), "job")
    assert "moe_topk_mass 0.31" in text and "moe_local_rows 65536" in text
