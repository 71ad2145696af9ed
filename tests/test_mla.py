"""Latent attention (``Mla``) and the deepseek_v3 stack (Kanana-2-30B-A3B's
family) at a small size on the CPU: interleaved rotary over part of a head
against a loop over pairs, the ``Mla`` layer and the flash kernels with a
V head narrower than Q's and K's against plain attention, the program's
``TransformerLM`` against the plain reference
``benchmark/reference/deepseek_v3.py`` on seeded weights (forward,
gradients, three Adam steps), the expert share arithmetic beside two
shared experts, what refuses the stack, and the step's parts table.

Sizes: d 64, 4 heads with Q/K 24 wide (16 + a rotary 8) and V 12, a
latent 32 wide, dense MLP 96, 32 experts 16 wide top-6 with 2 shared,
T 32; a leading dense layer and two expert layers.
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
from benchmark.families.deepseek_v3 import lm_config  # noqa: E402
from benchmark.reference import common as refcommon  # noqa: E402
from benchmark.reference import deepseek_v3 as ref  # noqa: E402
from ddl_tpu.models.transformer import (  # noqa: E402
    LMConfig, Mla, MoeMlp, Rope, TransformerLM, _rope,
)
from ddl_tpu.ops.attention import dense_attention  # noqa: E402
from ddl_tpu.ops.flash_attention import flash_attention  # noqa: E402

F32 = refcommon.caster("f32")

# Both sides compute in float32 on the CPU at the highest precision; what
# is left is the order of float32 sums (1e-6 relative on a leaf's largest
# entry).  1e-4 leaves two orders of room and is orders under what a wrong
# split, pair, norm, scale or route reads.
RTOL = 1e-4


def small_model(held=32, share=0, **over):
    m = dict(
        vocab_size=96, d_model=64, n_layers=3, n_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12, d_ff=96, moe_d_ff=16,
        num_experts=32, experts_held=held, expert_share_index=share, expert_top_k=6,
        num_shared_experts=2, num_dense_layers=1, route_scale=2.448, rope_theta=10000,
        norm_eps=1e-6, compute_dtype="float32", flash=False, remat=False,
    )
    m.update(over)
    return m


def ref_mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def unbox_init(module, *args):
    return flax.core.meta.unbox(
        jax.eval_shape(lambda: module.init(jax.random.key(0), *args))["params"])


# ---------------------------------------------------------------- rotary


def test_interleaved_rotary_turns_each_pair_in_place():
    """``Rope(interleave=True)`` over a 64-wide rotary part: pair (2i,
    2i + 1) turns by ``p * theta^(-2i/64)``, each pair on its own, as the
    reference's loop over pairs does; q.k of two rotated heads equals the
    de-interleaved (deepseek_v3's ``apply_rotary_pos_emb_interleave``)
    layout's."""
    x = jax.random.normal(jax.random.key(0), (2, 40, 3, 64))
    y = jax.random.normal(jax.random.key(1), (2, 40, 3, 64))
    rope = Rope(1e6, interleave=True)
    got = _rope(x, rope)
    np.testing.assert_allclose(got, ref.rotate_pairs(x, 1e6), rtol=0, atol=2e-6)
    by_hand = np.array(x, np.float64)
    for p in range(40):
        for i in range(32):
            a, b = by_hand[:, p, :, 2 * i].copy(), by_hand[:, p, :, 2 * i + 1].copy()
            ang = p * 1e6 ** (-2 * i / 64)
            by_hand[:, p, :, 2 * i] = a * math.cos(ang) - b * math.sin(ang)
            by_hand[:, p, :, 2 * i + 1] = a * math.sin(ang) + b * math.cos(ang)
    np.testing.assert_allclose(got, by_hand, rtol=0, atol=2e-5)

    def deinterleaved(v):  # the published function's layout: evens, then odds
        v = v.reshape(*v.shape[:-1], 32, 2).swapaxes(-1, -2).reshape(v.shape)
        return _rope(v, Rope(1e6))

    np.testing.assert_allclose(
        jnp.einsum("bphd,bqhd->bhpq", got, _rope(y, rope)),
        jnp.einsum("bphd,bqhd->bhpq", deinterleaved(x), deinterleaved(y)),
        rtol=0, atol=1e-4)


def test_the_half_split_rotary_is_what_it_was():
    """Every configuration without ``interleave`` rotates pair (i, i +
    half) exactly as before the interleaved path existed."""
    x = jax.random.normal(jax.random.key(2), (2, 16, 3, 32))
    for rope in (Rope(10000.0), Rope(1e6, factor=8.0, original=64, attention_factor=1.2)):
        freqs = rope.inv_freq(16)
        ang = jnp.arange(16, dtype=jnp.float32)[:, None] * freqs
        cos, sin = (f(ang) * rope.attention_factor for f in (jnp.cos, jnp.sin))
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
        x1, x2 = x[..., :16], x[..., 16:]
        want = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        np.testing.assert_array_equal(_rope(x, rope), want)


# ------------------------------------------------------------ the layer


def test_mla_layer_against_the_reference_in_value_and_gradient():
    model = small_model()
    cfg = lm_config(model)
    x = jax.random.normal(jax.random.key(3), (2, 32, 64))
    cot = jax.random.normal(jax.random.key(4), (2, 32, 64))
    flat = ref.init_params(jax.random.key(5), model)
    p = {k[len("block1/"):]: v for k, v in flat.items() if k.startswith("block1/mla/")}
    layer = Mla(cfg, None, 0, cfg.layer_rope(1))
    template = unbox_init(layer, x)
    tree = unflatten_like(template, {k[len("mla/"):]: v for k, v in p.items()})
    assert set(flatten(template)) == {"q/kernel", "kv_a/kernel", "kv_norm/scale",
                                      "kv_b/kernel", "out/kernel"}

    def ours(x, tree):
        return jnp.sum(layer.apply({"params": tree}, x) * cot)

    def theirs(x, p):
        return jnp.sum(ref.latent_attention(ref_mm, x, p, model) * cot)

    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": tree}, x)
        g_ours = jax.jit(jax.grad(ours, (0, 1)))(x, tree)
    want = ref.latent_attention(ref_mm, x, p, model)
    np.testing.assert_allclose(got, want, rtol=0, atol=RTOL * float(jnp.abs(want).max()))
    g_theirs = jax.jit(jax.grad(theirs, (0, 1)))(x, p)
    np.testing.assert_allclose(g_ours[0], g_theirs[0], rtol=0,
                               atol=RTOL * float(jnp.abs(g_theirs[0]).max()))
    for name, g in flatten(g_ours[1]).items():
        w = g_theirs[1]["mla/" + name]
        np.testing.assert_allclose(g, w, rtol=0, atol=RTOL * float(jnp.abs(w).max()),
                                   err_msg=name)
    # causal: a change at the last position moves nothing before it
    bumped = layer.apply({"params": tree}, x.at[:, -1].add(1.0))
    np.testing.assert_allclose(bumped[:, :-1], got[:, :-1], rtol=0, atol=1e-6)
    # the rotary key is every head's: a K built without it reads a gap
    no_rope = Mla(cfg, None, 0, None).apply({"params": tree}, x)
    assert float(jnp.abs(no_rope - got).max()) > 1e-2


@pytest.mark.parametrize("d,dv,t,block", [(48, 32, 64, 32), (192, 128, 128, 64)],
                         ids=["d48-dv32", "d192-dv128"])
def test_flash_with_a_v_head_narrower_than_q_and_k_matches_dense(d, dv, t, block):
    """Q and K wider than V (latent attention's 192 over 128, and a
    smaller pair), as many K/V heads as query heads, causal over two Q and
    two K blocks: the output and dV take V's width, dQ and dK Q's, and all
    four agree with the dense core; the scale is Q's width."""
    ks = jax.random.split(jax.random.key(6), 4)
    q = jax.random.normal(ks[0], (2, t, 3, d))
    k = jax.random.normal(ks[1], (2, t, 3, d))
    v = jax.random.normal(ks[2], (2, t, 3, dv))
    w = jax.random.normal(ks[3], (2, t, 3, dv))
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=block, block_k=block)
    dense = lambda q, k, v: dense_attention(q, k, v, causal=True)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        out = flash(q, k, v)
        assert out.shape == (2, t, 3, dv)
        np.testing.assert_allclose(out, dense(q, k, v), rtol=1e-5, atol=1e-5)
        got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5, err_msg=name)
    # the softmax's scale is 1/sqrt(d): scores of unit q.k rows scaled by Q's width
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)[0, 0] / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf), -1)
    np.testing.assert_allclose(out[0, :, 0], p @ v[0, :, 0], rtol=1e-4, atol=1e-5)


# ------------------------------------------------- the stack, both sides


def program_and_weights(model, seed=3, t=32):
    cfg = lm_config(model)
    lm = TransformerLM(cfg)
    tok = jax.random.randint(jax.random.key(seed + 1), (2, t + 1), 0, model["vocab_size"])
    inp, tgt = tok[:, :-1], tok[:, 1:]
    template = unbox_init(lm, inp)
    flat = ref.init_params(jax.random.key(seed), model)
    return lm, flat, unflatten_like(template, flat), inp, tgt


def program_loss(lm, inp, tgt):
    def loss_of(p):
        logits, _ = lm.apply({"params": p}, inp)
        lse = jax.scipy.special.logsumexp(logits, -1)
        return (lse - jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]).mean(), logits

    return loss_of


@pytest.fixture(scope="module")
def both_sides():
    """Logits, loss and gradients of program and reference, once: the
    second share of four (experts 8-15 of 32 held)."""
    model = small_model(8, 1)
    lm, flat, params, inp, tgt = program_and_weights(model)
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(
            program_loss(lm, inp, tgt), has_aux=True))(params)
    ref_loss, ref_grads = jax.jit(ref.make_grad_fn(model, F32))(flat, (inp, tgt))
    ref_logits = jax.jit(lambda p: ref.forward_logits(p, inp, model, F32))(flat)
    return dict(logits=logits, loss=loss, grads=flatten(grads), ref_logits=ref_logits,
                ref_loss=ref_loss, ref_grads=ref_grads)


@pytest.mark.parametrize("which", ["logits", "loss"])
def test_program_agrees_with_the_reference(both_sides, which):
    s = both_sides
    got, want = s[which], s["ref_" + which]
    assert float(jnp.abs(got - want).max()) <= RTOL * float(jnp.abs(want).max())


def test_program_gradients_agree_with_the_reference_leaf_by_leaf(both_sides):
    got, want = both_sides["grads"], both_sides["ref_grads"]
    assert set(got) == set(want)
    assert {"lm_head/kernel", "block0/mla/kv_norm/scale", "block2/moe/shared/wg/kernel"} <= set(got)
    for name, w in want.items():
        norm = float(jnp.linalg.norm(w))
        if name.endswith("moe/bias"):  # not trained: no gradient on either side
            assert norm == 0.0 and float(jnp.abs(got[name]).max()) == 0.0, name
            continue
        assert norm > 0.0, name
        assert abs(float(jnp.linalg.norm(got[name])) - norm) <= RTOL * norm, name
        assert float(jnp.abs(got[name] - w).max()) <= RTOL * float(jnp.abs(w).max()), name


def test_three_adam_steps_of_loss_and_gradients_follow_the_reference():
    """Three steps of Adam from the same weights over three batches: each
    step's loss and every leaf's gradient agree; so do the weights after."""
    model = small_model(8, 0)
    lm, flat, params, _, _ = program_and_weights(model)
    toks = jax.random.randint(jax.random.key(11), (3, 2, 33), 0, model["vocab_size"])
    opt = dict(learning_rate=1e-3, b1=0.9, b2=0.999, eps=1e-8)
    ref_grad = jax.jit(ref.make_grad_fn(model, F32))
    with jax.default_matmul_precision("highest"):
        grad = jax.jit(lambda p, inp, tgt: jax.value_and_grad(
            program_loss(lm, inp, tgt), has_aux=True)(p))
    ours, theirs = flatten(params), dict(flat)
    mu = {k: jnp.zeros_like(v) for k, v in theirs.items()}
    nu, mu_r, nu_r = dict(mu), dict(mu), dict(mu)
    for step in range(3):
        inp, tgt = toks[step, :, :-1], toks[step, :, 1:]
        with jax.default_matmul_precision("highest"):
            (loss, _), g = grad(unflatten_like(params, ours), inp, tgt)
        ref_loss, ref_g = ref_grad(theirs, (inp, tgt))
        assert abs(float(loss) - float(ref_loss)) <= RTOL * abs(float(ref_loss)), step
        g = flatten(g)
        for name, w in ref_g.items():
            scale = max(float(jnp.abs(w).max()), 1e-30)
            assert float(jnp.abs(g[name] - w).max()) <= RTOL * scale, (step, name)
        ours, mu, nu = refcommon.adam_update(ours, g, mu, nu, step + 1, opt)
        theirs, mu_r, nu_r = refcommon.adam_update(theirs, ref_g, mu_r, nu_r, step + 1, opt)
    for name, w in theirs.items():
        np.testing.assert_allclose(ours[name], w, rtol=0, atol=1e-5, err_msg=name)


# ------------------------------------------------------- the expert layer


def layer_weights(model, seed=5):
    flat = ref.init_params(jax.random.key(seed), model)
    p = {k[len("block1/"):]: v for k, v in flat.items() if k.startswith("block1/moe/")}
    # a bias of the scores' spread, so that it moves choices
    p["moe/bias"] = p["moe/bias"] / 0.02 * 0.3
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


def test_the_sixteen_shares_and_the_shared_experts_add_up_to_the_uncut_layer():
    """``expert_share`` (0, 16) .. (15, 16), 2 of 32 experts each: the
    routed parts of the sixteen shares' outputs, with the two shared
    experts' SwiGLU (which every share computes alike) counted once, equal
    the reference's layer with all 32 held."""
    model = small_model()
    p, x = layer_weights(model)
    m = x.reshape(-1, model["d_model"])
    whole = ref.moe_layer(ref_mm, m, p, model)
    shared = ref._swiglu(ref_mm, m, p["moe/shared/wg/kernel"], p["moe/shared/wi/kernel"],
                         p["moe/shared/wo/kernel"])
    total, rows = shared, 0.0
    with jax.default_matmul_precision("highest"):
        for i in range(16):
            part = dict(p, **{k: p[k][2 * i:2 * i + 2] for k in ("moe/wg", "moe/wi", "moe/wo")})
            cfg = lm_config(small_model(2, i))
            assert cfg.expert_share == (i, 16) and cfg.num_shared_experts == 2
            y, col = MoeMlp(cfg).apply({"params": as_tree(part)}, x, mutable=["intermediates"])
            counters = {k: float(v[0]) for k, v in col["intermediates"].items()}
            assert counters["moe_rows_dropped"] == 0.0
            rows += counters["moe_local_rows"]
            total = total + y[0].reshape(m.shape) - shared
    assert rows == 6 * 64  # every choice lands on exactly one share
    assert float(jnp.abs(total - whole).max()) <= RTOL * float(jnp.abs(whole).max())
    # the routed scaling factor is in it: at 1 the routed part shrinks
    plain = ref.moe_layer(ref_mm, m, p, dict(model, route_scale=1.0))
    assert float(jnp.abs((plain - shared) * 2.448 - (whole - shared)).max()) <= (
        RTOL * float(jnp.abs(whole).max()))


# ------------------------------------------------------------ what refuses it


def test_serving_refuses_a_latent_attention_stack_naming_r3():
    from ddl_tpu.serve.engine import make_serve_step_fns

    cfg = lm_config(small_model())
    assert cfg.mla and not cfg.recurrent and not cfg.layer_types
    with pytest.raises(NotImplementedError, match="latent attention.*ROADMAP R3"):
        make_serve_step_fns(cfg, block_size=8, num_blocks=8, max_batch=2)


@pytest.mark.parametrize("over,match", [
    (dict(moe_layer="capacity", moe_router="softmax", num_shared_experts=0, route_scale=1.0,
          expert_share=(0, 1)), "capacity expert layer"),
    (dict(attn_impl="ring"), "ring, Ulysses"),
    (dict(attn_impl="ulysses"), "ring, Ulysses"),
    (dict(n_kv_heads=2), "n_kv_heads"),
    (dict(qk_norm=True), "no differential heads"),
    (dict(qk_rope_head_dim=7), "even qk_rope_head_dim"),
    (dict(v_head_dim=0), "all > 0"),
])
def test_what_latent_attention_is_not_built_beside(over, match):
    cfg = lm_config(small_model())
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(cfg, **over)


def test_the_pipeline_refuses_latent_attention():
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns
    from ddl_tpu.train.state import build_optimizer

    cfg = LMConfig(vocab_size=96, d_model=64, n_layers=2, n_heads=4, d_ff=96,
                   compute_dtype="float32", kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=12)
    assert cfg.layers_alike
    with pytest.raises(NotImplementedError, match="latent attention is built for make_lm_step_fns"):
        make_lm_step_fns(cfg, LMMeshSpec(pipe=2), build_optimizer(1e-3), jax.random.key(0), 4, 16)


# ---------------------------------------------------- the step's parts


def test_parts_table_of_a_compiled_step_names_the_latent_attention(tmp_path):
    """The step's second table tags latent attention (``mla``, the flash
    kernels' calls among it) and its latent path (``mla/latent``) apart
    from the MLPs, the expert layers and the head."""
    from ddl_tpu.obs import hbm
    from ddl_tpu.obs.events import EventWriter
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import STEP_PARTS, make_lm_step_fns
    from ddl_tpu.train.state import build_optimizer

    cfg = lm_config(small_model(8, 1))
    fns = make_lm_step_fns(cfg, LMMeshSpec(), build_optimizer(1e-5), jax.random.key(0), 2, 32)
    state, tok = fns.init_state(), jnp.zeros((2, 32), jnp.int32)
    writer = EventWriter(str(tmp_path), "parts", host=0)
    hbm.plan_program(writer, "train_step", fns.train, (state, tok, tok), parts=STEP_PARTS)
    parts = hbm.scope_table("train_step.parts")
    counts = {tag: list(parts.values()).count(tag) for tag in set(parts.values())}
    assert counts.get("mla", 0) > 0 and counts.get("mla/latent", 0) > 0, counts
    assert {"mlp", "moe/experts", "moe/shared", "head"} <= set(counts)
    assert not {"attn", "ssm", "conv", "xattn"} & set(counts)
    _, m = fns.train(state, tok, tok)
    assert float(m["moe_rows_dropped"]) == 0.0 and float(m["moe_local_rows"]) > 0
