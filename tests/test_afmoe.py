"""The afmoe block (ISSUE 27) at a small size on the CPU: the program's
``TransformerLM`` built from ``LMConfig``'s per-layer fields against the
plain reference ``benchmark/reference/afmoe.py`` on seeded weights, the
dropless expert layer's share arithmetic and counters, the per-layer
attention kinds, the grouped product against a ``jnp`` loop, the work
functions against hand arithmetic, and the parts table of a compiled step.

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
from ddl_tpu.models.transformer import (  # noqa: E402
    Block, LMConfig, MoeMlp, TransformerLM, dropless_plan,
)
from ddl_tpu.ops import grouped_matmul as gm  # noqa: E402

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
        {"params": params}, tok, init_kv_cache(cfg, 2, 16), 0, mutable=["intermediates"])[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(NotImplementedError, match="mixed sliding and full"):
        patterned = lm_config(small_model(n_layers=2, layer_types=["sliding_attention", "full_attention"]))
        LMDecode(patterned).init(jax.random.key(0), tok, init_kv_cache(patterned, 2, 16), 0)


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


@pytest.mark.parametrize("over", [
    dict(layer_types=["sliding_attention", "full_attention"]),
    dict(layer_types=[], sliding_window=0),  # dense layers, q/k norms, gate, four norms
], ids=["pattern", "afmoe_block"])
def test_the_serving_engine_refuses_a_block_it_does_not_build(over):
    from ddl_tpu.serve.engine import make_serve_step_fns

    cfg = lm_config(small_model(n_layers=2, **over))
    with pytest.raises(NotImplementedError, match="serving block"):
        make_serve_step_fns(cfg, block_size=8, num_blocks=8, max_batch=2)


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
    assert set(parts.values()) == {*STEP_PARTS.values(), "other"}
    assert set(direction.values()) <= {"fwd", "bwd", "update"}  # interpreted kernels: no custom call
    # the parts table does not displace the direction table by module
    assert list(hbm.scope_tables().values()) == [direction]
    state, m = fns.train(state, tok, tok)
    assert float(m["moe_rows_dropped"]) == 0.0 and float(m["moe_local_rows"]) > 0
    assert float(m["moe_load_max_over_mean"]) >= 1.0


def test_a_kernel_takes_the_part_it_is_called_in():
    from ddl_tpu.obs import scope
    from ddl_tpu.train.lm_steps import STEP_PARTS

    text = """HloModule jit_step, entry_computation_layout={()->f32[]}

ENTRY %main () -> f32[] {
  %p = bf16[8,8]{1,0} parameter(0)
  %moe_gmm_fwd.1 = bf16[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(LM)/block1/moe/moe._dropless/moe/experts/moe_gmm_fwd/pallas_call"}
  %flash.2 = bf16[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(LM))/block1/attn/flash_bwd_dq/pallas_call"}
  %f.3 = f32[] fusion(%flash.2), kind=kLoop, metadata={op_name="jit(step)/jvp(LM)/head/reduce_sum"}
  %f.4 = f32[] fusion(%f.3), kind=kLoop, metadata={op_name="jit(step)/jvp(LM)/lm_head/dot_general"}
  ROOT %f.5 = f32[] fusion(%f.4), kind=kLoop, metadata={op_name="jit(step)/adam/mul"}
}
"""
    assert scope.parts_table(text, STEP_PARTS) == {
        "moe_gmm_fwd.1": "moe/experts", "flash.2": "attn", "f.3": "head", "f.4": "head",
        "f.5": "other"}
    assert scope.scope_table(text)["moe_gmm_fwd.1"] == "kernel/moe_gmm_fwd"
    # a program that opens none of the caller's scopes, or a caller with
    # none, has no second table
    assert scope.parts_table(text, {"encoder": "encoder"}) == {}
    assert scope.parts_table(text, {}) == {}


def test_the_period_event_carries_the_dropless_counters(tmp_path):
    from ddl_tpu.obs import EventWriter, StepTrace, read_events

    w = EventWriter(tmp_path, "job", host=0)
    trace = StepTrace(w)
    trace.begin_period(0)
    trace.end_period(0, 5, elapsed=1.0, steps=5, metrics={
        "loss": 9.5, "ce": 9.5, "moe_local_rows": 16384.0,
        "moe_load_max_over_mean": 1.25, "moe_rows_dropped": 0.0})
    trace.begin_period(1)
    trace.end_period(1, 10, elapsed=1.0, steps=5, metrics={"loss": 9.4, "moe_aux": 0.0})
    w.close()
    first, second = [e for e in read_events(w.path) if e["kind"] == "period"]
    assert (first["moe_local_rows"], first["moe_load_max_over_mean"],
            first["moe_rows_dropped"]) == (16384.0, 1.25, 0.0)
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
