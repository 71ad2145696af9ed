"""The lfm2_moe stack (LFM2-8B-A1B's family) at a small size on the CPU:
the gated short convolution (``ShortConv``) against its equations written
out, the program's ``TransformerLM`` against the plain reference
``benchmark/reference/lfm2.py`` on seeded weights, the expert share
arithmetic, the selection bias, the refusal to serve, the step's parts
table and the sharding table.

Sizes: d 64, 4 x 16 query heads on 2 K/V heads, dense MLP 96, 8 experts
32 wide top-4, 3 taps, T 32; layers conv (dense), full attention, conv,
conv, conv (experts), as the committed configuration's.
"""

import dataclasses
import math
import os
import sys

import flax
import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families.common import flatten, unflatten_like  # noqa: E402
from benchmark.families.lfm2 import lm_config  # noqa: E402
from benchmark.reference import common as refcommon  # noqa: E402
from benchmark.reference import lfm2 as ref  # noqa: E402
from ddl_tpu.models.transformer import (  # noqa: E402
    CausalConv, LMConfig, MoeMlp, ShortConv, TransformerLM,
)

F32 = refcommon.caster("f32")
PATTERN = ["conv", "full_attention", "conv", "conv", "conv"]

# Both sides compute in float32 on the CPU at the highest precision; what
# is left is the order of float32 sums (1e-6 relative on a leaf's largest
# entry).  1e-4 leaves two orders of room and is orders under what a wrong
# split, tap, norm, rotary or route reads.
RTOL = 1e-4


def small_model(held=8, share=0, **over):
    m = dict(
        vocab_size=96, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=96, moe_d_ff=32, num_experts=8, experts_held=held, expert_share_index=share,
        expert_top_k=4, num_dense_layers=1, route_scale=1.0, route_eps=1e-6, short_conv=3,
        layer_types=list(PATTERN), rope_theta=1000000, norm_eps=1e-5,
        compute_dtype="float32", flash=False, remat=False,
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


def ref_mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


# ------------------------------------------------------- the conv mixer


def _written_out(x, w_in, taps, w_out):
    """The published gated short convolution, position by position: the
    projection split B, C, x in that order, B * x, out_t = sum over the
    taps of w_j (B * x)_{t - L + 1 + j} (nothing before position 0, no
    bias), then C * that, then the out projection."""
    d = x.shape[-1]
    bcx = ref_mm("btd,de->bte", x, w_in)
    bg, cg, xs = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    u = bg * xs
    length, t = taps.shape[0], x.shape[1]
    v = jnp.stack([
        sum(taps[j] * u[:, s - length + 1 + j] for j in range(length) if s - length + 1 + j >= 0)
        for s in range(t)], axis=1)
    return ref_mm("btd,de->bte", cg * v, w_out)


def test_short_conv_against_its_equations_in_value_and_gradient():
    cfg = LMConfig(d_model=32, compute_dtype="float32", short_conv=3)
    k = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(k[0], (2, 9, 32))
    p = {"in_proj": {"kernel": jax.random.normal(k[1], (32, 96)) / math.sqrt(32)},
         "conv": {"kernel": jax.random.normal(k[2], (3, 32)) / math.sqrt(3)},
         "out_proj": {"kernel": jax.random.normal(k[3], (32, 32)) / math.sqrt(32)}}
    cot = jax.random.normal(k[4], (2, 9, 32))
    template = flax.core.meta.unbox(
        jax.eval_shape(lambda: ShortConv(cfg).init(jax.random.key(1), x))["params"])
    assert jax.tree.map(lambda a: a.shape, template) == jax.tree.map(lambda a: a.shape, p)

    def ours(x, p):
        return jnp.sum(ShortConv(cfg).apply({"params": p}, x) * cot)

    def theirs(x, p):
        return jnp.sum(_written_out(x, p["in_proj"]["kernel"], p["conv"]["kernel"],
                                    p["out_proj"]["kernel"]) * cot)

    with jax.default_matmul_precision("highest"):
        got = ShortConv(cfg).apply({"params": p}, x)
        g_ours = jax.grad(ours, (0, 1))(x, p)
    want = _written_out(x, p["in_proj"]["kernel"], p["conv"]["kernel"], p["out_proj"]["kernel"])
    np.testing.assert_allclose(got, want, rtol=0, atol=RTOL * float(jnp.abs(want).max()))
    g_theirs = jax.grad(theirs, (0, 1))(x, p)
    for a, b in zip(jtu.tree_leaves(g_ours), jtu.tree_leaves(g_theirs)):
        np.testing.assert_allclose(a, b, rtol=0, atol=RTOL * float(jnp.abs(b).max()))
    # causal: a change at the last position moves nothing before it
    bumped = ShortConv(cfg).apply({"params": p}, x.at[:, -1].add(1.0))
    np.testing.assert_array_equal(bumped[:, :-1], got[:, :-1])
    # the split's order matters: B and x swap freely (B * x), C does not
    swapped = dict(p, in_proj={"kernel": jnp.concatenate(
        [p["in_proj"]["kernel"][:, 32:64], p["in_proj"]["kernel"][:, :32],
         p["in_proj"]["kernel"][:, 64:]], axis=1)})
    assert float(jnp.abs(ShortConv(cfg).apply({"params": swapped}, x) - got).max()) > 1e-2


def test_causal_conv_keeps_its_bias_unless_told_not_to():
    x = jax.random.normal(jax.random.key(0), (1, 6, 8))
    leaves = lambda conv: set(flatten(flax.core.meta.unbox(  # noqa: E731
        jax.eval_shape(lambda: conv.init(jax.random.key(0), x))["params"])))
    assert leaves(CausalConv(4)) == {"kernel", "bias"}
    assert leaves(CausalConv(4, use_bias=False)) == {"kernel"}
    w = jax.random.normal(jax.random.key(1), (4, 8))
    b = jax.random.normal(jax.random.key(2), (8,))
    padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    taps = sum(padded[:, j:j + 6] * w[j] for j in range(4))
    np.testing.assert_array_equal(
        CausalConv(4).apply({"params": {"kernel": w, "bias": b}}, x), b + taps)
    np.testing.assert_array_equal(
        CausalConv(4, use_bias=False).apply({"params": {"kernel": w}}, x), taps)


# ------------------------------------------------- the stack, both sides


@pytest.fixture(scope="module")
def both_sides():
    """Logits, loss and gradients of program and reference, once: the five
    layers of the committed pattern, the second share of four (experts 2
    and 3 of 8 held)."""
    model = small_model(2, 1)
    lm, flat, params, inp, tgt = program_and_weights(model)

    def loss_of(p):
        logits, _ = lm.apply({"params": p}, inp)
        lse = jax.scipy.special.logsumexp(logits, -1)
        return (lse - jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]).mean(), logits

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(loss_of, has_aux=True))(params)
    ref_loss, ref_grads = jax.jit(ref.make_grad_fn(model, F32))(flat, (inp, tgt))
    ref_logits = jax.jit(lambda p: ref.forward_logits(p, inp, model, F32))(flat)
    return dict(logits=logits, loss=loss, grads=flatten(grads), ref_logits=ref_logits,
                ref_loss=ref_loss, ref_grads=ref_grads)


@pytest.mark.parametrize("which", ["logits", "loss"])
def test_program_agrees_with_the_reference(both_sides, which):
    s = both_sides
    got, want = s[which], s["ref_" + which]
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= RTOL * scale


def test_program_gradients_agree_with_the_reference_leaf_by_leaf(both_sides):
    got, want = both_sides["grads"], both_sides["ref_grads"]
    assert set(got) == set(want)
    assert "lm_head/kernel" not in got and "block0/conv/conv/kernel" in got
    for name, w in want.items():
        norm = float(jnp.linalg.norm(w))
        if name.endswith("moe/bias"):  # not trained: no gradient on either side
            assert norm == 0.0 and float(jnp.abs(got[name]).max()) == 0.0, name
            continue
        assert norm > 0.0, name
        assert abs(float(jnp.linalg.norm(got[name])) - norm) <= RTOL * norm, name
        assert float(jnp.abs(got[name] - w).max()) <= RTOL * float(jnp.abs(w).max()), name


# ------------------------------------------------------- the expert layer


def moe_layer(cfg, x, layer_params):
    y, col = MoeMlp(cfg).apply({"params": layer_params}, x, mutable=["intermediates"])
    return y[0], {k: float(v[0]) for k, v in col["intermediates"].items()}


def layer_weights(model, seed=5, bias_scale=None):
    flat = ref.init_params(jax.random.key(seed), model)
    p = {k[len("block1/"):]: v for k, v in flat.items() if k.startswith("block1/moe/")}
    if bias_scale is not None:
        p["moe/bias"] = p["moe/bias"] / 0.02 * bias_scale
    x = jax.random.normal(jax.random.key(seed + 1), (2, 32, model["d_model"]), jnp.float32)
    return p, x


def as_tree(p):
    tree = {}
    for k, v in p.items():
        *path, leaf = k[len("moe/"):].split("/")
        node = tree
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = v
    return tree


def test_the_four_shares_add_up_to_the_uncut_layer():
    """``expert_share`` (0, 4) .. (3, 4), 2 experts each: their outputs
    summed (nothing is computed by every share alike: no shared expert)
    equal the reference's layer with all 8 held."""
    model = small_model()
    p, x = layer_weights(model)
    m = x.reshape(-1, model["d_model"])
    whole = ref.moe_layer(ref_mm, m, p, model)
    total, rows = jnp.zeros_like(whole), 0.0
    with jax.default_matmul_precision("highest"):
        for i in range(4):
            part = dict(p, **{k: p[k][2 * i:2 * i + 2] for k in ("moe/wg", "moe/wi", "moe/wo")})
            cfg = lm_config(small_model(2, i))
            assert cfg.expert_share == (i, 4) and cfg.route_eps == 1e-6
            y, counters = moe_layer(cfg, x, as_tree(part))
            assert counters["moe_rows_dropped"] == 0.0
            rows += counters["moe_local_rows"]
            total = total + y.reshape(m.shape)
    assert rows == 4 * 64  # every choice lands on exactly one share
    assert float(jnp.abs(total - whole).max()) <= RTOL * float(jnp.abs(whole).max())


def test_a_selection_bias_moves_the_choices_and_the_program_follows():
    """A bias of the size of the scores' spread chooses other experts than
    the scores alone; the program and the reference choose alike, and the
    chosen experts are weighted by their unbiased scores over their sum
    plus 1e-6."""
    model = small_model()
    p, x = layer_weights(model, bias_scale=0.3)
    m = np.asarray(x, np.float64).reshape(-1, 64)
    s = 1.0 / (1.0 + np.exp(-(m @ np.asarray(p["moe/router/kernel"], np.float64))))
    b = np.asarray(p["moe/bias"], np.float64)
    by_score = np.argsort(-s, axis=1)[:, :4]
    by_biased = np.argsort(-(s + b), axis=1)[:, :4]
    moved = [set(a) != set(c) for a, c in zip(by_score, by_biased)]
    assert sum(moved) >= 8, "the bias should move some tokens' choices"
    with jax.default_matmul_precision("highest"):
        y, _ = moe_layer(lm_config(model), x, as_tree(p))
    want = ref.moe_layer(ref_mm, x.reshape(-1, 64), p, model)
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape), want,
                               rtol=0, atol=RTOL * float(jnp.abs(want).max()))
    # by hand, token by token: the unbiased scores of the biased choice
    wg, wi, wo = (np.asarray(p[k], np.float64) for k in ("moe/wg", "moe/wi", "moe/wo"))
    hand = np.zeros_like(m)
    for n, row in enumerate(m):
        top = by_biased[n]
        for e in top:
            g = row @ wg[e]
            swiglu = (g / (1 + np.exp(-g)) * (row @ wi[e])) @ wo[e]
            hand[n] += s[n, e] / (s[n, top].sum() + 1e-6) * swiglu
    np.testing.assert_allclose(np.asarray(y).reshape(hand.shape), hand,
                               rtol=0, atol=RTOL * np.abs(hand).max())


def test_the_normalisation_epsilon_is_a_field_the_capacity_layer_refuses():
    assert LMConfig().route_eps == 1e-20  # the afmoe and mellum blocks' steps unchanged
    with pytest.raises(ValueError, match="route_eps are the dropless layer's"):
        LMConfig(num_experts=8, route_eps=1e-6)
    model = small_model()
    p, x = layer_weights(model)
    cfg = lm_config(model)
    with jax.default_matmul_precision("highest"):
        tiny, _ = moe_layer(cfg, x, as_tree(p))
        big, _ = moe_layer(dataclasses.replace(cfg, route_eps=1.0), x, as_tree(p))
    # with an epsilon of the scores' own size the weights shrink visibly
    assert float(jnp.abs(big).max()) < 0.9 * float(jnp.abs(tiny).max())


# ------------------------------------------------------------ what refuses it


def test_a_conv_stack_refuses_a_decode_cache_with_the_recurrent_message():
    from ddl_tpu.serve.engine import make_serve_step_fns

    cfg = lm_config(small_model())
    assert cfg.recurrent == ("conv",)
    with pytest.raises(NotImplementedError, match="its conv layers keep a recurrent state.*R4"):
        make_serve_step_fns(cfg, block_size=8, num_blocks=8, max_batch=2)
    with pytest.raises(ValueError, match="short_conv >= 1"):
        LMConfig(n_layers=1, layer_types=("conv",), short_conv=0)


# ---------------------------------------------------- spans and sharding


def test_parts_table_of_a_compiled_step_names_the_conv_mixer(tmp_path):
    """The step's second table tags the conv mixers' projections (``conv``)
    and their elementwise part (``conv/short``) apart from the attention,
    the dense MLP, the expert layers and the head."""
    from ddl_tpu.obs import hbm
    from ddl_tpu.obs.events import EventWriter
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import STEP_PARTS, make_lm_step_fns
    from ddl_tpu.train.state import build_optimizer

    cfg = lm_config(small_model(2, 1))
    fns = make_lm_step_fns(cfg, LMMeshSpec(), build_optimizer(1e-5), jax.random.key(0), 2, 32)
    state, tok = fns.init_state(), jnp.zeros((2, 32), jnp.int32)
    writer = EventWriter(str(tmp_path), "parts", host=0)
    hbm.plan_program(writer, "train_step", fns.train, (state, tok, tok), parts=STEP_PARTS)
    parts = hbm.scope_table("train_step.parts")
    assert set(parts) == set(hbm.scope_table("train_step"))
    counts = {tag: list(parts.values()).count(tag) for tag in set(parts.values())}
    assert counts.get("conv", 0) > 0 and counts.get("conv/short", 0) > 0, counts
    assert {"attn", "mlp", "moe/experts", "head"} <= set(counts)
    assert not {"ssm", "ssm/scan", "gmu", "xattn", "moe/shared"} & set(counts)
    _, m = fns.train(state, tok, tok)
    assert float(m["moe_rows_dropped"]) == 0.0 and float(m["moe_local_rows"]) > 0


def test_the_mamba_layer_s_convolution_stays_under_ssm():
    from ddl_tpu.obs import scope
    from ddl_tpu.train.lm_steps import STEP_PARTS

    text = """HloModule jit_step, entry_computation_layout={()->f32[]}

ENTRY %main () -> f32[] {
  %p = f32[8,8]{1,0} parameter(0)
  %f.1 = f32[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/jvp(LM)/block1/ssm/conv/mul"}
  %f.2 = f32[8,8]{1,0} fusion(%f.1), kind=kLoop, metadata={op_name="jit(step)/jvp(LM)/block2/conv/in_proj/dot_general"}
  %f.3 = f32[8,8]{1,0} fusion(%f.2), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp(LM))/block2/conv/short/conv/mul"}
  ROOT %f.4 = f32[] fusion(%f.3), kind=kLoop, metadata={op_name="jit(step)/jvp(LM)/block2/conv/short/mul"}
}
"""
    assert scope.parts_table(text, STEP_PARTS) == {
        "f.1": "ssm", "f.2": "conv", "f.3": "conv/short", "f.4": "conv/short"}


@pytest.mark.parametrize("fsdp", [False, True])
def test_lfm2_table_matches_logical_resolution(fsdp):
    """The conv mixer's three leaves each have a rule, and it is the one
    their logical annotations resolve to; a tied stack has no lm_head."""
    from ddl_tpu.parallel import rules as R
    from tests.test_partition_rules import _assert_table_matches_logical, _lm_mesh

    cfg = dataclasses.replace(lm_config(small_model()), fsdp=fsdp)
    abs_params = jax.eval_shape(
        lambda r: TransformerLM(cfg, None).init(r, jnp.zeros((4, 8), jnp.int32))["params"],
        jax.random.key(0))
    names = {R.tree_path_str(p) for p, _ in
             jtu.tree_leaves_with_path(flax.core.meta.unbox(abs_params))}
    assert {"block0/conv/in_proj/kernel", "block0/conv/conv/kernel",
            "block0/conv/out_proj/kernel", "block1/moe/bias"} <= names
    assert not any(n.startswith("lm_head") or n.endswith("conv/bias") for n in names)
    _assert_table_matches_logical(abs_params, R.lm_rules(fsdp), fsdp, _lm_mesh())


def test_obs_hbm_prints_the_gated_pair_s_column_blocks_of_1792(tmp_path):
    """At the cell's widths the gated pair's VMEM reckoning fits both
    banks' whole blocks (2 x 2048 x 1792 bf16, 14.7 MB, double-buffered
    beside the row tiles), so its forward takes F in one block of 1792 and
    its dx K in one of 2048, and the plan says so."""
    from ddl_tpu.obs.events import EventWriter
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.obs.hbm import account_from_fold, render_hbm
    from ddl_tpu.ops import grouped_matmul as gm

    block = gm._gated_tile(1792, 2048, gm.ROW_TILE, 2, dx=False)
    assert block == 1792 and gm._gated_tile(2048, 1792, gm.ROW_TILE, 2, dx=True) == 2048
    col = gm._col_metadata(1792, block)
    assert col == {"col1792": 1792}
    tiles = {"moe_gmm_fwd": {"calls": 8, "total": 2 * 4 * 136, "floor": 48, "products": 12,
                             **col}}
    w = EventWriter(tmp_path, "plan", host=0)
    w.emit("hbm_plan", label="train_step", analysis="memory_analysis", argument_bytes=4096,
           output_bytes=4096, temp_bytes=512, alias_bytes=0, code_bytes=64, kernel_tiles=tiles)
    w.emit("hbm_sample", params_bytes=600, watermark=2000, peak=2000, limit=4096, synthetic=True)
    w.close()
    out = render_hbm(account_from_fold(fold_job(tmp_path, "plan", cache=False)), "plan")
    assert "tiles moe_gmm_fwd: 8 call(s)" in out and "column blocks 1792 of 1792" in out
