"""The afmoe block (ISSUE 27) at a small size on the CPU: the program's
``TransformerLM`` built from ``LMConfig``'s per-layer fields against the
plain reference ``benchmark/reference/afmoe.py`` on seeded weights, the
dropless expert layer's share arithmetic and counters, the per-layer
attention kinds, the grouped product against a ``jnp`` loop, the row
kernels of the shuffle against the ``jnp.take`` gathers they replaced, the
work functions against hand arithmetic, and the parts table of a compiled
step.

Sizes: d 64, 4 x 16 heads, 2 K/V heads, 8 experts top-2, 1 shared, window
8, T 32, layers dense-sliding, sliding, sliding, sliding, full.
"""

import json
import os
import subprocess
import sys

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families.afmoe import lm_config  # noqa: E402
from benchmark.families.common import flatten, unflatten_like  # noqa: E402
from benchmark.reference import afmoe as ref  # noqa: E402
from benchmark.reference import common as refcommon  # noqa: E402
from benchmark.work import afmoe as work  # noqa: E402
from ddl_tpu.models import transformer  # noqa: E402
from ddl_tpu.models.transformer import (  # noqa: E402
    Block, LMConfig, MoeMlp, TransformerLM, dropless_plan,
)
from ddl_tpu.ops import grouped_matmul as gm  # noqa: E402
from ddl_tpu.ops import moe_rows  # noqa: E402

F32 = refcommon.caster("f32")


def small_model(held=8, share=0, **over):
    m = dict(
        vocab_size=96, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=192, moe_d_ff=32, num_experts=8, experts_held=held, expert_share_index=share,
        expert_top_k=2, num_shared_experts=1, route_scale=2.826, num_dense_layers=1,
        layer_types=["sliding_attention"] * 4 + ["full_attention"], sliding_window=8,
        rope_theta=10000, norm_eps=1e-5, compute_dtype="float32", flash=False, remat=False,
    )
    m.update(over)
    return m


def program_and_weights(model, seed=3, t=32):
    """The program's module, the reference's flat weights from the seed,
    the same weights as the program's tree, and a batch."""
    cfg = lm_config(model)
    lm = TransformerLM(cfg)
    tok = jax.random.randint(jax.random.key(seed + 1), (2, t + 1), 0, model["vocab_size"])
    inp, tgt = tok[:, :-1], tok[:, 1:]
    template = flax.core.meta.unbox(
        jax.eval_shape(lambda: lm.init(jax.random.key(0), inp))["params"])
    flat = ref.init_params(jax.random.key(seed), model)
    return lm, flat, unflatten_like(template, flat), inp, tgt


# ------------------------------------------------ program against reference

# Tolerance: both sides compute in float32 on the CPU (the program's
# compute_dtype is float32 here, its products forced to the highest
# precision); what is left is the order of float32 sums, 1e-6 relative on
# a leaf's largest entry.  1e-4 leaves two orders of room and is four
# orders under what a wrong weight, mask or norm reads.
RTOL = 1e-4


@pytest.mark.parametrize("held,share", [(8, 0), (2, 1)], ids=["all_held", "a_share"])
def test_program_agrees_with_the_reference(held, share):
    model = small_model(held, share)
    lm, flat, params, inp, tgt = program_and_weights(model)

    def loss_of(p):
        logits, _ = lm.apply({"params": p}, inp)
        lse = jax.scipy.special.logsumexp(logits, -1)
        return (lse - jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]).mean()

    with jax.default_matmul_precision("highest"):
        logits, _ = lm.apply({"params": params}, inp)
        loss, grads = jax.value_and_grad(loss_of)(params)
    ref_logits = ref.forward_logits(flat, inp, model, F32)
    ref_loss, ref_grads = ref.make_grad_fn(model, F32)(flat, (inp, tgt))
    scale = float(jnp.abs(ref_logits).max())
    assert float(jnp.abs(logits - ref_logits).max()) <= RTOL * scale
    assert abs(float(loss) - float(ref_loss)) <= RTOL * abs(float(ref_loss))
    got = flatten(grads)
    assert set(got) == set(ref_grads)
    for name, want in ref_grads.items():
        top = float(jnp.abs(want).max())
        if name.endswith("moe/bias"):  # selection only: no gradient, either side
            assert top == 0.0 and float(jnp.abs(got[name]).max()) == 0.0
            continue
        assert top > 0.0, name
        assert float(jnp.abs(got[name] - want).max()) <= RTOL * top, name


# ------------------------------------------------------- the expert layer


def moe_layer(cfg, x, layer_params):
    y, col = MoeMlp(cfg).apply({"params": layer_params}, x, mutable=["intermediates"])
    return y[0], {k: float(v[0]) for k, v in col["intermediates"].items()}


def layer_weights(model, seed=5):
    """One expert layer's weights (the reference's names without the
    block prefix) and normed-looking inputs."""
    one = dict(model, n_layers=1, num_dense_layers=0, layer_types=["sliding_attention"])
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


def test_the_shares_add_up_to_the_uncut_layer():
    """4 shares of 2 experts: the routed parts summed, the shared expert
    counted once, equal the reference's layer with all 8 held."""
    model = small_model()
    p, x = layer_weights(model)
    m = x.reshape(-1, model["d_model"])
    whole = ref._moe(ref_mm, m, p, model)
    shared = ref._swiglu(ref_mm, m, p["moe/shared/wg/kernel"], p["moe/shared/wi/kernel"],
                         p["moe/shared/wo/kernel"])
    total = jnp.zeros_like(whole)
    with jax.default_matmul_precision("highest"):
        for i in range(4):
            part = dict(p, **{k: p[k][2 * i:2 * i + 2] for k in ("moe/wg", "moe/wi", "moe/wo")})
            cfg = lm_config(small_model(2, i))
            y, counters = moe_layer(cfg, x, as_tree(part))
            assert counters["moe_rows_dropped"] == 0.0
            total = total + (y.reshape(m.shape) - shared)
    assert float(jnp.abs(total + shared - whole).max()) <= RTOL * float(jnp.abs(whole).max())


def test_dropless_under_skew():
    """A router that sends every token to one held expert: that expert
    takes 8 times the mean, nothing is dropped, the output is the
    reference's, and the counter reads the skew."""
    model = small_model(16, num_experts=16)
    p, x = layer_weights(model)
    p["moe/router/kernel"] = p["moe/router/kernel"].at[:, 3].set(0.0)
    p["moe/bias"] = p["moe/bias"].at[3].set(5.0)  # the selection's, not the weights'
    with jax.default_matmul_precision("highest"):
        y, counters = moe_layer(lm_config(model), x, as_tree(p))
    want = ref._moe(ref_mm, x.reshape(-1, model["d_model"]), p, model)
    assert counters["moe_rows_dropped"] == 0.0
    assert counters["moe_local_rows"] == 2 * 64  # every choice is held here
    assert counters["moe_load_max_over_mean"] == pytest.approx(8.0)
    assert float(jnp.abs(y.reshape(want.shape) - want).max()) <= RTOL * float(jnp.abs(want).max())


def test_the_bias_moves_the_selection_and_never_the_weights():
    model = small_model()
    p, x = layer_weights(model)
    cfg = lm_config(model)
    with jax.default_matmul_precision("highest"):
        plain, _ = moe_layer(cfg, x, as_tree(dict(p, **{"moe/bias": jnp.zeros(8)})))
        shifted, _ = moe_layer(cfg, x, as_tree(dict(p, **{"moe/bias": jnp.full(8, 0.7)})))
        forced, counters = moe_layer(
            cfg, x, as_tree(dict(p, **{"moe/bias": jnp.zeros(8).at[5].set(9.0)})))
    # one shift for all: the same selection, and the weights do not see it
    assert float(jnp.abs(plain - shifted).max()) == 0.0
    # a bias on one expert: every token now chooses it (max over mean of
    # 8 held experts under top-2: 64 of 128 choices on one, 4 times 16)
    assert counters["moe_load_max_over_mean"] == pytest.approx(4.0)
    assert float(jnp.abs(plain - forced).max()) > 1e-3
    want = ref._moe(ref_mm, x.reshape(-1, 64), dict(p, **{"moe/bias": jnp.zeros(8).at[5].set(9.0)}),
                    model)
    assert float(jnp.abs(forced.reshape(want.shape) - want).max()) <= RTOL * float(jnp.abs(want).max())


def test_the_plan_places_every_held_choice_once():
    idx = jax.random.randint(jax.random.key(0), (40, 2), 0, 8).astype(jnp.int32)
    plan = dropless_plan(idx, lo=2, held=3, tile=8)
    held = np.asarray(plan["held"])
    rows = np.asarray(plan["choice_row"])[held]
    assert held.sum() == int(((idx >= 2) & (idx < 5)).sum()) == int(plan["counts"].sum())
    assert len(set(rows.tolist())) == len(rows)  # a row each
    valid = np.asarray(plan["row_valid"])
    assert valid.sum() == held.sum() and valid[rows].all()
    # a row's choice points back at the row, and lies in its expert's run
    back = np.asarray(plan["row_choice"])[rows]
    assert (np.asarray(plan["choice_row"]).reshape(-1)[back] == rows).all()
    group = np.asarray(plan["tile_group"])[rows // 8]
    assert (group == np.asarray(idx).reshape(-1)[back] - 2).all()


# ------------------------------------------------------ attention by layer


def one_block(kind):
    cfg = LMConfig(
        vocab_size=32, d_model=64, n_layers=1, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, compute_dtype="float32", remat=False, layer_types=(kind,),
        attn_window=8, qk_norm=True, attn_gate=True,
        mlp_gated=True, sandwich_norm=True, norm_eps=1e-5,
    )
    block = Block(cfg, None, 0)
    x = jax.random.normal(jax.random.key(1), (1, 32, 64), jnp.float32)
    params = block.init(jax.random.key(2), x)["params"]
    return (lambda x: block.apply({"params": params}, x)[0]), x


@pytest.mark.parametrize("kind,sees", [("sliding_attention", False), ("full_attention", True)])
def test_a_key_beyond_the_window_reaches_only_the_full_layer(kind, sees):
    """Position 20's output against position 5's input, window 8: only
    attention crosses positions, so the gradient is the band's."""
    f, x = one_block(kind)
    g = jax.grad(lambda x: f(x)[0, 20].sum())(x)
    far, near = float(jnp.abs(g[0, 5]).max()), float(jnp.abs(g[0, 15]).max())
    assert near > 0.0 and (far > 0.0) == sees
    assert float(jnp.abs(g[0, 21:]).max()) == 0.0  # causal either way


@pytest.mark.parametrize("kind,moves", [("sliding_attention", True), ("full_attention", False)])
def test_only_the_sliding_layer_knows_positions(kind, moves):
    """Two earlier inputs exchanged: a full layer rotates nothing, so the
    last position's output cannot tell (the sum over visible keys is the
    same); a sliding layer's rotary scores change."""
    f, x = one_block(kind)
    swapped = x.at[0, 27].set(x[0, 29]).at[0, 29].set(x[0, 27])
    gap = float(jnp.abs(f(x)[0, 31] - f(swapped)[0, 31]).max())
    assert (gap > 1e-4) if moves else (gap < 1e-5)


def test_a_pattern_hands_each_layer_its_own_window_to_the_core():
    seen = []

    def core(q, k, v, window):
        seen.append(window)
        from ddl_tpu.ops.attention import dense_attention
        return dense_attention(q, k, v, causal=True, window=window)

    cfg = lm_config(small_model())
    tok = jnp.zeros((1, 16), jnp.int32)
    lm = TransformerLM(cfg, core)
    lm.apply(lm.init(jax.random.key(0), tok), tok)
    assert seen[-5:] == [8, 8, 8, 8, 0]
    with pytest.raises(ValueError):
        LMConfig(n_layers=2, layer_types=("sliding_attention",))


# ------------------------------------------------------ the grouped product


@pytest.mark.parametrize("counts", [[5, 0, 17, 8], [0, 0, 0, 0], [8, 8, 8, 8], [1, 30, 0, 2]],
                         ids=["ragged", "all_empty", "on_the_tile", "off_the_tile"])
def test_grouped_product_against_a_loop(counts):
    """Interpret mode against a ``jnp`` loop over groups: empty groups,
    sizes on and off the tile, rows beyond the groups' sum (left
    unwritten, so compared where a row is valid), forward and both
    gradients."""
    tile, groups, k, n = 8, 4, 16, 24
    counts = jnp.array(counts, jnp.int32)
    rows = gm.buffer_rows(40, groups, tile)
    start, tg, ts, na = gm.align_groups(counts, rows // tile, tile)
    assert int(na[0]) == sum(max(1, -(-c // tile)) for c in counts.tolist())
    r = jnp.arange(rows)
    g = tg[r // tile]
    valid = ((r - start[g] < counts[g]) & (r // tile < na[0]))[:, None]
    x = jnp.where(valid, jax.random.normal(jax.random.key(0), (rows, k)), 0.0)
    w = jax.random.normal(jax.random.key(1), (groups, k, n))
    dy = jnp.where(valid, jax.random.normal(jax.random.key(2), (rows, n)), 0.0)

    def loss(fn):
        return lambda x, w: jnp.sum(jnp.where(valid, fn(x, w), 0.0) * dy)

    ours = lambda x, w: gm.grouped_matmul(x, w, tg, ts, na, tile=tile)  # noqa: E731
    loop = lambda x, w: gm.grouped_matmul_reference(x, w, tg, na, tile=tile)  # noqa: E731
    assert float(jnp.abs(jnp.where(valid, ours(x, w) - loop(x, w), 0.0)).max()) < 1e-4
    gx, gw = jax.grad(loss(ours), (0, 1))(x, w)
    rx, rw = jax.grad(loss(loop), (0, 1))(x, w)
    assert float(jnp.abs(jnp.where(valid, gx - rx, 0.0)).max()) < 1e-4
    assert float(jnp.abs(gw - rw).max()) < 1e-4  # an empty group's gradient is written: zeros
    assert gw.dtype == w.dtype


# ------------------------------------------------------------ the row kernels

# The gathers as the package had them before the row kernels (ISSUE 28): a
# ``jnp.take`` over the whole buffer or over every choice, masked after.


def take_gather(x, plan, k):
    return jnp.where(plan["row_valid"][:, None], jnp.take(x, plan["row_choice"] // k, axis=0), 0)


def take_gather_bwd(g, plan):
    choice_row, held = plan["choice_row"], plan["held"]
    dx = jnp.zeros((choice_row.shape[0], g.shape[1]), jnp.float32)
    for j in range(choice_row.shape[1]):
        picked = jnp.take(g, choice_row[:, j], axis=0).astype(jnp.float32)
        dx = dx + jnp.where(held[:, j, None], picked, 0.0)
    return dx.astype(g.dtype)


def take_combine(o, w, plan):
    choice_row, held = plan["choice_row"], plan["held"]
    y = jnp.zeros((w.shape[0], o.shape[1]), jnp.float32)
    for j in range(w.shape[1]):
        picked = jnp.take(o, choice_row[:, j], axis=0).astype(jnp.float32)
        y = y + jnp.where(held[:, j, None], picked * w[:, j, None], 0.0)
    return y


def take_combine_bwd(o, w, plan, g):
    choice_row, held = plan["choice_row"], plan["held"]
    k = w.shape[1]
    dw = jnp.stack([
        jnp.where(held[:, j],
                  (jnp.take(o, choice_row[:, j], axis=0).astype(jnp.float32) * g).sum(-1), 0.0)
        for j in range(k)
    ], axis=1)
    scale = jnp.take(w.reshape(-1), plan["row_choice"])[:, None]
    do = jnp.where(plan["row_valid"][:, None],
                   jnp.take(g, plan["row_choice"] // k, axis=0) * scale, 0.0)
    return do.astype(o.dtype), dw.astype(w.dtype)


ROUTINGS = ["uniform", "none_held", "one_expert", "all_held", "on_the_tile"]


def routing(kind, n=48, k=2, experts=8, lo=2, held=3, tile=8):
    """(n, k) choices over ``experts`` of which ``[lo, lo + held)`` are
    held: the seed's uniform lot, no choice held, every token's first
    choice on one held expert, every choice held (k = held: the buffer
    full), and runs that end exactly on a tile boundary."""
    if kind == "uniform":
        idx = jnp.argsort(jax.random.uniform(jax.random.key(4), (n, experts)), axis=1)[:, :k]
    elif kind == "none_held":
        idx = jnp.tile(jnp.array([0, lo + held]), (n, 1))
    elif kind == "one_expert":
        idx = jnp.tile(jnp.array([lo + 1, 0]), (n, 1))
    elif kind == "all_held":
        k = held
        idx = jnp.tile(jnp.arange(lo, lo + held), (n, 1))
    else:  # tile rows on each of two held experts, none on the third
        idx = jnp.where((jnp.arange(n) < tile)[:, None], jnp.array([lo, lo + 2]), jnp.array([0, 1]))
    return dropless_plan(idx.astype(jnp.int32), lo, held, tile), n, k


def valid_rows(plan, x):
    return jnp.where(plan["row_valid"][:, None], x, 0)


def buffer_like(plan, key, d, dtype):
    """A buffer as the grouped products leave it: values where a row
    holds a choice, zeros where it pads an active tile."""
    rows = plan["row_valid"].shape[0]
    return valid_rows(plan, jax.random.normal(key, (rows, d), jnp.float32)).astype(dtype)


# The largest |kernel - float64 sum| of the weighted combine's float32
# result on these routings at the parent (PR 33), whose selection carried
# the weight as three bf16 addends: a weight times a row is now rounded
# once, so no routing may read larger.
PARENT_COMBINE_ERROR = {
    "f32": {"uniform": 1.92e-7, "none_held": 0.0, "one_expert": 2.19e-7,
            "all_held": 4.55e-7, "on_the_tile": 3.51e-7},
    "bf16": {"uniform": 1.57e-7, "none_held": 0.0, "one_expert": 2.28e-7,
             "all_held": 4.05e-7, "on_the_tile": 2.37e-7},
}


def combine_in_float64(o, w, plan):
    choice_row, held = np.asarray(plan["choice_row"]), np.asarray(plan["held"])
    o64 = np.asarray(o.astype(jnp.float32), np.float64)
    w64 = np.asarray(w, np.float64)
    y = np.zeros((w.shape[0], o.shape[1]))
    for j in range(w.shape[1]):
        y += np.where(held[:, j, None], o64[choice_row[:, j]] * w64[:, j, None], 0.0)
    return y


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ROUTINGS)
def test_row_kernels_against_the_take_gathers(kind, dtype):
    """Values and every VJP of ``_rows_gather`` and ``_rows_combine``
    (interpret mode) against the ``jnp.take`` formulations, compared where
    a buffer row is written (an active tile): exact for the 0/1
    selections, float32 rounding for the weighted sums.  The combine
    leaves in the compute type, with and without a shared addend: the
    float32 sum cast once, its cotangent in that type against the
    formulas fed the same values in float32."""
    plan, n, k = routing(kind)
    d = 16
    loose = 1e-2 if dtype == jnp.bfloat16 else 1e-6
    x = jax.random.normal(jax.random.key(0), (n, d), jnp.float32).astype(dtype)
    w = jax.random.uniform(jax.random.key(1), (n, k), jnp.float32, 0.1, 1.0)
    o = buffer_like(plan, jax.random.key(2), d, dtype)
    g_rows = buffer_like(plan, jax.random.key(3), d, dtype)
    g_tok = jax.random.normal(jax.random.key(5), (n, d), jnp.float32).astype(dtype)
    shared = jax.random.normal(jax.random.key(6), (n, d), jnp.float32).astype(dtype)
    tile = plan["pairs"]["tok"].shape[-1]
    written = jnp.repeat(jnp.arange(o.shape[0] // tile) < plan["n_active"][0], tile)[:, None]
    assert bool((plan["row_valid"][:, None] <= written).all())

    xs, gather_vjp = jax.vjp(lambda x: transformer._rows_gather(x, plan), x)
    assert xs.dtype == dtype
    np.testing.assert_array_equal(jnp.where(written, xs, 0), take_gather(x, plan, k))
    (dx,) = gather_vjp(g_rows)
    assert dx.dtype == dtype
    np.testing.assert_allclose(dx.astype(jnp.float32), take_gather_bwd(g_rows, plan).astype(jnp.float32),
                               rtol=loose, atol=1e-6)

    # the float32 sum itself, as the kernel hands it over when asked to
    y32 = moe_rows.rows_combine(
        o, plan["pairs"], tokens=n, groups=plan["counts"].shape[0], out_dtype=jnp.float32,
        weights=transformer._row_weights(w, plan))
    np.testing.assert_allclose(y32, take_combine(o, w, plan), rtol=1e-6, atol=1e-6)
    error = float(np.abs(np.asarray(y32, np.float64) - combine_in_float64(o, w, plan)).max())
    assert error <= PARENT_COMBINE_ERROR["bf16" if dtype == jnp.bfloat16 else "f32"][kind]

    want_do, want_dw = take_combine_bwd(o, w, plan, g_tok.astype(jnp.float32))
    for add in ((), (shared,)):
        y, combine_vjp = jax.vjp(lambda o, w, *add: transformer._rows_combine(o, w, plan, *add), o, w, *add)
        assert y.dtype == dtype
        want_y = take_combine(o, w, plan) + sum(a.astype(jnp.float32) for a in add)
        if not add:
            np.testing.assert_array_equal(y, y32.astype(dtype))  # one cast, of that sum
        np.testing.assert_allclose(y.astype(jnp.float32), want_y.astype(dtype).astype(jnp.float32),
                                   rtol=loose, atol=1e-6)
        do, dw, *dadd = combine_vjp(g_tok)
        assert do.dtype == dtype and dw.dtype == w.dtype
        np.testing.assert_allclose(jnp.where(written, do, 0).astype(jnp.float32),
                                   want_do.astype(jnp.float32), rtol=loose, atol=1e-6)
        np.testing.assert_allclose(dw, want_dw, rtol=1e-5, atol=1e-5)
        for da in dadd:  # the shared experts' cotangent is the token's own
            np.testing.assert_array_equal(da, g_tok)


@pytest.mark.parametrize("kind", ROUTINGS)
def test_a_token_holds_at_most_one_row_of_a_row_tile(kind):
    """What the weighted combine rests on (``moe_rows.rows_combine``): it
    selects a tile's rows and then multiplies by the token's one weight
    there, so a token with two rows in a tile would be weighed wrongly."""
    plan, _, _ = routing(kind)
    tok = np.asarray(plan["pairs"]["tok"])
    tok = tok.reshape(-1, tok.shape[-1])
    assert (tok >= 0).sum() == int(plan["row_valid"].sum())
    for row_tile in tok:
        held = row_tile[row_tile >= 0]
        assert len(np.unique(held)) == len(held)


@pytest.mark.parametrize("kind", ROUTINGS)
def test_the_pairs_cover_every_routed_row_and_stay_in_their_bound(kind):
    plan, n, k = routing(kind)
    pairs = plan["pairs"]
    tile = pairs["tok"].shape[-1]
    tt = moe_rows.token_tile(n, tile)
    tok = np.asarray(pairs["tok"]).reshape(-1, tile)
    pi, pj, used = (np.asarray(a) for a in pairs["by_row"])
    assert len(pi) == moe_rows.pairs_bound(tok.shape[0], n // tt, plan["counts"].shape[0])
    listed = set(zip(pi[:used[0]].tolist(), pj[:used[0]].tolist()))
    assert len(listed) == used[0]  # no pair twice
    shared = {(i, t // tt) for i in range(tok.shape[0]) for t in tok[i] if t >= 0}
    assert shared <= listed
    # every active row tile is written, no other is touched
    assert {i for i, _ in listed} == set(range(int(plan["n_active"][0])))
    assert (pi[used[0]:] == pi[used[0] - 1]).all() and (pj[used[0]:] == pj[used[0] - 1]).all()
    qi, qj, entries = (np.asarray(a) for a in pairs["by_token"])
    assert entries[0] == used[0] + n // tt
    runs = qj[:entries[0]]
    assert (np.diff(runs) >= 0).all() and set(runs.tolist()) == set(range(n // tt))
    opens = np.r_[True, np.diff(runs) > 0]
    assert sorted(zip(qi[:entries[0]][~opens].tolist(), runs[~opens].tolist())) == sorted(listed)


def test_rows_of_inactive_tiles_are_never_read(monkeypatch):
    """NaN in every row of the tiles past ``n_active`` of ``xs``, of the
    grouped products' results (``o`` among them) and of the cotangents that
    come back to them (``do`` among them): the layer's output and every
    gradient are finite and equal to the run without the poison."""
    model = small_model(2, 1)
    p, x = layer_weights(model)
    cfg = lm_config(model)
    seen = []

    def spoiling(fn, n_active_of, poison):
        def spoil(a, n_active):  # what an unwritten tile may hold
            tiles = jnp.arange(a.shape[0]) // gm.ROW_TILE
            seen.append(a.shape[0] // gm.ROW_TILE - n_active[0])
            return jnp.where((tiles >= n_active[0])[:, None], poison, a)

        @jax.custom_vjp
        def spoiled(a, n_active):
            return spoil(a, n_active)

        spoiled.defvjp(lambda a, n: (spoil(a, n), n), lambda n, g: (spoil(g, n), None))
        return lambda *args, **kw: spoiled(fn(*args, **kw), n_active_of(*args))

    def run(poison):
        with monkeypatch.context() as m:
            m.setattr(transformer, "_rows_gather", spoiling(
                transformer._rows_gather, lambda x, plan: plan["n_active"], poison))
            m.setattr(gm, "grouped_matmul", spoiling(
                gm.grouped_matmul, lambda x, w, tg, ts, na: na, poison))

            def loss(params, x):
                y, _ = MoeMlp(cfg).apply({"params": params}, x, mutable=["intermediates"])
                return jnp.sum(y[0] ** 2), y[0]

            return jax.value_and_grad(loss, (0, 1), has_aux=True)(as_tree(p), x)

    clean, dirty = run(0.0), run(jnp.nan)
    assert min(int(n) for n in seen) >= 1  # a tile was there to poison
    for a, b in zip(jax.tree.leaves(clean), jax.tree.leaves(dirty)):
        assert bool(jnp.isfinite(b).all())
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------------- work


def committed_shapes():
    with open(os.path.join(ROOT, "benchmark", "configs", "trinity-mini.json")) as f:
        return dict(json.load(f)["model"], batch=2, seq_len=4096)


def test_required_work_against_hand_arithmetic():
    s = committed_shapes()
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 4 * 2048
    expert = 3 * 2048 * 1024
    hand = (2 * 25024 * 2048 + 2048 + 5 * attn + 3 * 2048 * 6144
            + 4 * (2048 * 128 + 128 + (8 + 1) * expert))
    assert work.param_count(s) == hand == 504_147_712
    assert work.param_count(dict(s, experts_held=16)) == 705_474_304  # ISSUE 27's share
    assert work.visible_keys(s, 0) == pytest.approx(1536.25)   # W(W+1)/2 + (T-W)W over T
    assert work.visible_keys(s, 4) == pytest.approx(2048.5)
    assert work.local_rows_per_layer(s) == 8192 * 8 * 8 / 128 == 4096
    per_token = (5 * 2 * (3 * 2048 * 4096 + 2 * 2048 * 512)
                 + 2 * 2 * 4096 * (4 * 1536.25 + 2048.5)
                 + 2 * 3 * 2048 * 6144
                 + 4 * (2 * 2048 * 128 + 2 * expert * 1.5)
                 + 2 * 2048 * 25024)
    assert work.forward_flops_per_token(s) == pytest.approx(per_token)
    assert work.train_step_flops(s) == pytest.approx(3 * per_token * 8192)
    gmm = work.expert_matmul_train(s)
    assert gmm["flops"] == pytest.approx(4 * 9 * 2 * 4096 * 2048 * 1024) and gmm["calls"] == 36
    flash = work.flash_attention_train(s)
    assert flash["flops"] == pytest.approx(6 * 2 * 128 * 4096 * 2 * 32 * (4 * 1536.25 + 2048.5))
    assert flash["calls"] == 15


def test_the_committed_configuration_keeps_every_published_width():
    with open(os.path.join(ROOT, "benchmark", "configs", "trinity-mini.json")) as f:
        c = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            pub = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")["config"]
        for k, v in pub.items():
            assert k in c["reduced"] or c[k] == v, k
    m = c["model"]
    assert (m["d_model"], m["n_heads"], m["head_dim"], m["n_kv_heads"]) == (2048, 32, 128, 4)
    assert (m["sliding_window"], m["d_ff"], m["moe_d_ff"], m["num_experts"]) == (2048, 6144, 1024, 128)
    assert (m["expert_top_k"], m["num_shared_experts"], m["route_scale"], m["norm_eps"]) == (
        8, 1, 2.826, 1e-5)
    assert c["experts_published"] == 128 and c["vocab_published"] == 200192
    assert c["num_experts"] == c["experts_held"] == m["experts_held"] >= 8
    assert m["vocab_size"] * 8 >= c["vocab_published"]


# ------------------------------- the paths that build blocks of their own


def test_decode_builds_each_layer_as_its_own_kind():
    """``LMDecode`` hands every block its index: a stack whose leading
    layer is dense and whose second is an expert layer (no pattern: a cache
    over mixed windows is not built, and ``Attention`` says so) prefills to
    the training forward's logits from the training tree."""
    from ddl_tpu.infer.decode import LMDecode, init_kv_cache

    cfg = lm_config(small_model(n_layers=2, layer_types=[], sliding_window=0))
    tok = jax.random.randint(jax.random.key(5), (2, 16), 0, cfg.vocab_size)
    lm = TransformerLM(cfg)
    params = flax.core.meta.unbox(lm.init(jax.random.key(0), tok)["params"])
    assert "mlp" in params["block0"] and "moe" in params["block1"]
    want, _ = lm.apply({"params": params}, tok, mutable=["intermediates"])[0]
    got, _ = LMDecode(cfg).apply(
        {"params": params}, tok, init_kv_cache(cfg, 2, 16), mutable=["intermediates"])[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(NotImplementedError, match="mixed sliding and full"):
        patterned = lm_config(small_model(n_layers=2, layer_types=["sliding_attention", "full_attention"]))
        LMDecode(patterned).init(jax.random.key(0), tok, init_kv_cache(patterned, 2, 16))


@pytest.mark.parametrize("over", [
    dict(layer_types=["sliding_attention", "full_attention"]),
    dict(layer_types=[], sliding_window=0),  # num_dense_layers 1 of 2
    dict(layer_types=[], sliding_window=0, num_dense_layers=0),  # the dropless layer alone
], ids=["pattern", "dense_layers", "dropless"])
def test_the_pipeline_refuses_layers_it_would_build_alike(over):
    from ddl_tpu.parallel.lm_pipeline import make_lm_pipeline_step_fns
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.state import build_optimizer

    cfg = lm_config(small_model(n_layers=2, **over))
    with pytest.raises(NotImplementedError, match="stacks one block"):
        make_lm_pipeline_step_fns(
            cfg, LMMeshSpec(pipe=2), build_optimizer(3e-4), jax.random.key(0), 2, 32, 2)


def test_the_serving_engine_refuses_a_layer_pattern_and_says_why():
    """What is left of the serving refusal: a cache over mixed windows, by
    the one ``NotImplementedError`` that ``Attention`` raises for any
    cache, raised by the factory before it builds a program."""
    from ddl_tpu.serve.engine import make_serve_step_fns

    cfg = lm_config(small_model(
        n_layers=2, layer_types=["sliding_attention", "full_attention"]))
    with pytest.raises(NotImplementedError, match="mixed sliding and full.*ROADMAP R2"):
        make_serve_step_fns(cfg, block_size=8, num_blocks=8, max_batch=2)


def test_the_serving_engine_serves_the_afmoe_block():
    """One block: the dense layer before the dropless expert layer, the
    shared expert, q/k norms, the output gate, four norms and the embedding
    multiplier are served because the engine stacks ``Block`` itself; its
    tokens are the sequential decoder's."""
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.serve.engine import ServeEngine
    from tests.test_serve import _clients, _sequential_tokens

    cfg = lm_config(small_model(n_layers=2, layer_types=[], sliding_window=0))
    assert cfg.moe_router == "sigmoid" and cfg.qk_norm and cfg.attn_gate
    params = flax.core.meta.unbox(
        TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert "mlp" in params["block0"] and "moe" in params["block1"]
    clients = [(cid, prompt % cfg.vocab_size, mn)
               for cid, prompt, mn in _clients(3, np.random.default_rng(5), new_hi=8)]
    eng = ServeEngine(cfg, params, LMMeshSpec(), block_size=8, num_blocks=32, max_batch=4)
    for cid, prompt, mn in clients:
        eng.submit(prompt, mn, request_id=cid, rng_seed=11)
    got = eng.run()
    want = _sequential_tokens(cfg, LMMeshSpec(), params, clients, seed=11)
    for cid in want:
        np.testing.assert_array_equal(got[cid], want[cid])


def test_the_pipeline_head_and_the_serving_block_read_norm_eps():
    """``norm_eps`` is the configuration's in every path that builds norms
    of its own: an eps large enough to show (0.5 against a mean square
    near 1) moves the pipeline's norm-only head as the formula says, and
    the serving engine still emits the sequential decoder's tokens."""
    from ddl_tpu.parallel.lm_pipeline import _HeadNorm
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.serve.engine import ServeEngine
    from tests.test_serve import _clients, _sequential_tokens

    cfg = LMConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=8, head_dim=8,
                   d_ff=256, compute_dtype="float32", norm_eps=0.5)
    x = jax.random.normal(jax.random.key(1), (2, 4, 64), jnp.float32)
    got = _HeadNorm(cfg).apply({"params": {"norm_f": {"scale": jnp.ones((64,))}}}, x)
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 0.5)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    params = flax.core.meta.unbox(
        TransformerLM(cfg, None).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    clients = _clients(4, np.random.default_rng(3))
    eng = ServeEngine(cfg, params, LMMeshSpec(), block_size=8, num_blocks=64, max_batch=4)
    for cid, prompt, mn in clients:
        eng.submit(prompt, mn, request_id=cid, rng_seed=11)
    got = eng.run()
    want = _sequential_tokens(cfg, LMMeshSpec(), params, clients, seed=11)
    for cid in want:
        np.testing.assert_array_equal(got[cid], want[cid])


# ---------------------------------------------------------- the parts table


def test_parts_table_of_a_compiled_step_names_every_tag(tmp_path):
    from ddl_tpu.obs import hbm
    from ddl_tpu.obs.events import EventWriter
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import STEP_PARTS, make_lm_step_fns
    from ddl_tpu.train.state import build_optimizer

    cfg = lm_config(small_model(2, 1))
    fns = make_lm_step_fns(cfg, LMMeshSpec(), build_optimizer(3e-4), jax.random.key(0), 2, 32)
    state, tok = fns.init_state(), jnp.zeros((2, 32), jnp.int32)
    writer = EventWriter(str(tmp_path), "parts", host=0)
    hbm.plan_program(writer, "train_step", fns.train, (state, tok, tok), parts=STEP_PARTS)
    parts, direction = hbm.scope_table("train_step.parts"), hbm.scope_table("train_step")
    assert set(parts) == set(direction)  # every ENTRY instruction, both ways
    # every tag but the hybrid stack's mixers' (tests/test_sambay.py has those)
    hybrid = {"ssm", "ssm/scan", "gmu", "xattn"}
    assert set(parts.values()) == {*STEP_PARTS.values(), "other"} - hybrid
    assert set(direction.values()) <= {"fwd", "bwd", "update"}  # interpreted kernels: no custom call
    # the parts table does not displace the direction table by module
    assert list(hbm.scope_tables().values()) == [direction]
    state, m = fns.train(state, tok, tok)
    assert float(m["moe_rows_dropped"]) == 0.0 and float(m["moe_local_rows"]) > 0
    assert float(m["moe_load_max_over_mean"]) >= 1.0
    assert 0.0 < float(m["moe_buffer_fill"]) <= 1.0  # row tiles in use over the buffer's


def test_a_kernel_takes_the_part_it_is_called_in():
    from ddl_tpu.obs import scope
    from ddl_tpu.train.lm_steps import STEP_PARTS

    text = """HloModule jit_step, entry_computation_layout={()->f32[]}

ENTRY %main () -> f32[] {
  %p = bf16[8,8]{1,0} parameter(0)
  %moe_gmm_fwd.1 = bf16[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(LM)/block1/moe/moe._dropless/moe/experts/moe_gmm_fwd/pallas_call"}
  %flash.2 = bf16[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(LM))/block1/attn/flash_bwd_dkv/pallas_call"}
  %rows.6 = bf16[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(LM)/block1/moe/moe._dropless/moe/dispatch/jit(_gather)/moe_rows_gather/pallas_call"}
  %rows.7 = bf16[8,8]{1,0} custom-call(%rows.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(LM))/block1/moe/moe._dropless/moe/combine/jit(_gather)/moe_rows_combine_bwd/pallas_call"}
  %f.3 = f32[] fusion(%flash.2), kind=kLoop, metadata={op_name="jit(step)/jvp(LM)/head/reduce_sum"}
  %f.4 = f32[] fusion(%f.3), kind=kLoop, metadata={op_name="jit(step)/jvp(LM)/lm_head/dot_general"}
  ROOT %f.5 = f32[] fusion(%f.4), kind=kLoop, metadata={op_name="jit(step)/adam/mul"}
}
"""
    assert scope.parts_table(text, STEP_PARTS) == {
        "moe_gmm_fwd.1": "moe/experts", "flash.2": "attn", "rows.6": "moe/dispatch",
        "rows.7": "moe/combine", "f.3": "head", "f.4": "head", "f.5": "other"}
    direction = scope.scope_table(text)
    assert direction["moe_gmm_fwd.1"] == "kernel/moe_gmm_fwd"
    assert (direction["rows.6"], direction["rows.7"]) == (
        "kernel/moe_rows_gather", "kernel/moe_rows_combine_bwd")
    # a program that opens none of the caller's scopes, or a caller with
    # none, has no second table
    assert scope.parts_table(text, {"encoder": "encoder"}) == {}
    assert scope.parts_table(text, {}) == {}


def test_the_period_event_carries_the_dropless_counters(tmp_path):
    from ddl_tpu.obs import EventWriter, StepTrace, read_events

    w = EventWriter(tmp_path, "job", host=0)
    trace = StepTrace(w)
    trace.begin_period()
    trace.end_period(0, 5, elapsed=1.0, steps=5, metrics={
        "loss": 9.5, "ce": 9.5, "moe_local_rows": 16384.0,
        "moe_load_max_over_mean": 1.25, "moe_rows_dropped": 0.0, "moe_buffer_fill": 0.09})
    trace.begin_period()
    trace.end_period(1, 10, elapsed=1.0, steps=5, metrics={"loss": 9.4, "moe_aux": 0.0})
    w.close()
    first, second = [e for e in read_events(w.path) if e["kind"] == "period"]
    assert (first["moe_local_rows"], first["moe_load_max_over_mean"],
            first["moe_rows_dropped"], first["moe_buffer_fill"]) == (16384.0, 1.25, 0.0, 0.09)
    assert first["loss"] == 9.5 and "ce" not in first
    assert not [k for k in second if k.startswith("moe_")]


# -------------------------------------------------------------- rehearsal


def test_the_new_cell_rehearses_on_the_cpu_without_a_device_metric(tmp_path):
    from tests.benchmark.test_afmoe_cell import tiny_afmoe_root

    root, cell = tiny_afmoe_root(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_REHEARSE_CPU="1")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", cell,
         "--seed", "2147484001", "--seconds", "0.5", "--trace", "1", "--root", root],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["metrics"] == {} and line["correct"] is False
    assert "memory_peak_bytes" not in line["device"] and line["device"]["platform"] == "cpu"
    assert not any("ms.train" in k or "roofline" in k or "mfu" in k
                   for k in line["rehearsal"]["counts"])
