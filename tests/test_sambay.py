"""The SambaY stack (ISSUE 31: Phi-4-mini-flash-reasoning's family) at a
small size on the CPU: the program's ``TransformerLM`` built from
``LMConfig``'s per-layer fields against the plain reference
``benchmark/reference/sambay.py`` on seeded weights, for a stack with all
five kinds of layer; the scan kernel (interpreted) against a sequential
scan; differential attention against the dense formula; the values that
travel down the stack; the tied head; the vocabulary's shares; what
refuses the configuration.

Sizes: d 32, 8 x 8 heads on 4 K/V heads (4 query pairs on 2 K/V pairs),
d_inner 64, state 4, 4 taps, rank 2, window 8, T 24, the layers of
published indices 15-19: sliding, mamba, full, gmu, cross.
"""

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
from benchmark.families.sambay import lm_config  # noqa: E402
from benchmark.reference import common as refcommon  # noqa: E402
from benchmark.reference import sambay as ref  # noqa: E402
from ddl_tpu.models.transformer import (  # noqa: E402
    LAYER_KINDS, Block, DiffAttention, LMConfig, TokenEmbed, TransformerLM,
    refuse_cache_over_layer_types,
)
from ddl_tpu.ops.selective_scan import selective_scan, selective_scan_reference  # noqa: E402

F32 = refcommon.caster("f32")
KINDS = ["sliding_attention", "mamba", "full_attention", "gmu", "cross_attention"]


def small_model(**over):
    m = dict(
        vocab_size=96, d_model=32, n_layers=5, n_heads=8, n_kv_heads=4, head_dim=8,
        d_ff=64, layer_types=list(KINDS), layer_indices=[15, 16, 17, 18, 19],
        sliding_window=8, norm_eps=1e-5, ssm_state=4, ssm_conv=4, ssm_expand=2,
        ssm_dt_rank=2, compute_dtype="float32", flash=False, remat=False,
    )
    m.update(over)
    return m


def program_and_weights(model, seed=3, t=24):
    """The program's module, the reference's flat weights from the seed,
    the same weights as the program's tree, and a batch."""
    lm = TransformerLM(lm_config(model))
    tok = jax.random.randint(jax.random.key(seed + 1), (2, t + 1), 0, model["vocab_size"])
    inp, tgt = tok[:, :-1], tok[:, 1:]
    template = flax.core.meta.unbox(
        jax.eval_shape(lambda: lm.init(jax.random.key(0), inp))["params"])
    flat = ref.init_params(jax.random.key(seed), model)
    return lm, flat, unflatten_like(template, flat), inp, tgt


def token_loss(logits, tgt):
    lse = jax.scipy.special.logsumexp(logits, -1)
    return (lse - jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]).mean()


# ------------------------------------------------ program against reference

# Tolerance: both sides compute in float32 on the CPU (the program's
# compute_dtype is float32 here, its products forced to the highest
# precision); what is left is the order of float32 sums, 1e-6 relative on
# a leaf's largest entry.  1e-4 leaves two orders of room and is four
# orders under what a wrong weight, mask, pairing or lambda reads.
RTOL = 1e-4


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_program_agrees_with_the_reference(remat):
    model = small_model(remat=remat)
    lm, flat, params, inp, tgt = program_and_weights(model)
    assert set(flatten(params)) == set(flat) == set(ref.leaf_names(model))
    with jax.default_matmul_precision("highest"):
        logits, _ = lm.apply({"params": params}, inp, mutable=["intermediates"])[0]
        loss, grads = jax.value_and_grad(lambda p: token_loss(
            lm.apply({"params": p}, inp, mutable=["intermediates"])[0][0], tgt))(params)
    ref_logits = ref.forward_logits(flat, inp, model, F32)
    ref_loss, ref_grads = ref.make_grad_fn(model, F32)(flat, (inp, tgt))
    assert float(jnp.abs(logits - ref_logits).max()) <= RTOL * float(jnp.abs(ref_logits).max())
    assert abs(float(loss) - float(ref_loss)) <= RTOL * abs(float(ref_loss))
    got = flatten(grads)
    for name, want in ref_grads.items():
        top = float(jnp.abs(want).max())
        if name.endswith("/k/bias"):
            # a key's bias moves every score of a row alike: softmax does
            # not see it, and neither side's gradient is more than noise
            assert top <= 1e-6 and float(jnp.abs(got[name]).max()) <= 1e-6, name
            continue
        assert top > 0.0, name
        assert float(jnp.abs(got[name] - want).max()) <= RTOL * top, name


def test_three_adam_steps_through_the_step_factory_follow_the_reference():
    """``make_lm_step_fns`` (the trainer's own step: flash kernels
    interpreted, the scan kernel interpreted, remat) against
    ``reference/common.three_steps`` from the same weights."""
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns
    from ddl_tpu.train.state import build_optimizer

    model = small_model(flash=True, remat=True, vocab_size=64)
    opt = dict(learning_rate=3e-4, b1=0.9, b2=0.999, eps=1e-8)
    tx = build_optimizer(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"])
    fns = make_lm_step_fns(lm_config(model), LMMeshSpec(), tx, jax.random.key(0), 2, 16)
    state = fns.init_state()
    flat = ref.init_params(jax.random.key(11), model)
    state = state.replace(params=unflatten_like(  # a copy: the step donates its state
        state.params, {k: jnp.array(v) for k, v in flat.items()}))
    toks = np.random.default_rng(5).integers(0, 64, (3, 2, 17)).astype(np.int32)
    batches = [(t[:, :-1], t[:, 1:]) for t in toks]
    want = refcommon.three_steps(ref, model, opt, flat, batches)
    losses = []
    with jax.default_matmul_precision("highest"):
        for inp, tgt in batches:
            state, m = fns.train(state, inp, tgt)
            losses.append(float(m["loss"]))
            assert float(m["ssm_state_absmax"]) >= 0.0
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-4)
    moved = {k: float(jnp.linalg.norm(v - flat[k])) for k, v in flatten(state.params).items()}
    for name, norm in want["delta_norms"].items():
        if want["grad_norms"][name] > 1e-6:  # a key's bias moves by round-off alone
            assert moved[name] == pytest.approx(norm, rel=2e-3), name


# ------------------------------------------------------- the scan kernel


def scan_args(b, t, d_in, n, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    u = jax.random.normal(k[0], (b, t, d_in))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, d_in)) - 1.0)
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (d_in, n)))
    bm, cm = jax.random.normal(k[3], (b, t, n)), jax.random.normal(k[4], (b, t, n))
    return (u, dt, a, bm, cm, jax.random.normal(k[5], (d_in,))), jax.random.normal(
        k[6], (b, t, d_in))


@pytest.mark.parametrize("t,chunk,block_d", [(20, 8, 8), (16, 8, 16), (5, 8, 16)],
                         ids=["ragged_3_chunks", "even_2_chunks", "under_one_chunk"])
def test_scan_kernel_matches_the_sequential_scan(t, chunk, block_d):
    """Forward and every gradient, interpreted: T that the chunk does not
    divide (the padded steps pass the state on), states carried across
    chunk boundaries and across blocks of d_in."""
    args, w = scan_args(2, t, 16, 4)
    kernel = lambda *a: selective_scan(*a, chunk=chunk, block_d=block_d)  # noqa: E731
    y, top = kernel(*args)
    want, _ = selective_scan_reference(*args)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a)[0] * w), argnums=tuple(range(6)))(*args)
    ref_g = jax.grad(lambda *a: jnp.sum(selective_scan_reference(*a)[0] * w),
                     argnums=tuple(range(6)))(*args)
    for name, g, r in zip(("u", "dt", "A", "B", "C", "D"), got, ref_g):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4 * float(jnp.abs(r).max()),
                                   err_msg=name)
    # the largest state at a chunk's start: none but the zero state when
    # the sequence is one chunk, the sequential scan's state after the
    # last whole chunk otherwise
    if t <= chunk:
        assert float(top) == 0.0
    else:
        assert 0.0 < float(top) <= float(selective_scan_reference(*args)[1]) * (1 + 1e-5)


def test_scan_kernel_metadata_names_grid_steps_and_time_steps():
    from ddl_tpu.ops.selective_scan import _metadata

    assert _metadata((2, 5, 32), 128) == {"tiles_total": "320", "tiles_steps": "40960"}


# ------------------------------------------------- differential attention


def dense_diff_attention(q, k, v, lam, window):
    """The formula as published, dense, one pair at a time."""
    b, t, h, dh = q.shape
    g = (h // 2) // (k.shape[2] // 2)
    pos = jnp.arange(t)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask &= pos[None, :] > pos[:, None] - window
    out = []
    for j in range(h // 2):
        kv = j // g
        probs = [jax.nn.softmax(jnp.where(
            mask, jnp.einsum("btd,bsd->bts", q[:, :, 2 * j + i], k[:, :, 2 * kv + i],
                             precision="highest") / np.sqrt(dh), -1e30), -1) for i in (0, 1)]
        vv = jnp.concatenate([v[:, :, 2 * kv], v[:, :, 2 * kv + 1]], -1)
        out.append(jnp.einsum("bts,bse->bte", probs[0] - lam * probs[1], vv,
                              precision="highest"))
    return jnp.stack(out, axis=2)  # (b, t, pairs, 2 dh)


@pytest.mark.parametrize("window", [8, 0], ids=["window", "full"])
@pytest.mark.parametrize("core", ["dense", "flash"])
def test_differential_attention_matches_the_dense_formula(window, core):
    cfg = lm_config(small_model(n_layers=1, layer_types=["sliding_attention"],
                                layer_indices=[15]))
    attn_core = None
    if core == "flash":
        from ddl_tpu.ops.flash_attention import flash_attention

        attn_core = lambda q, k, v, window: flash_attention(  # noqa: E731
            q, k, v, causal=True, window=window)
    layer = DiffAttention(cfg, attn_core, window, cfg.layer_lam0(0))
    x = jax.random.normal(jax.random.key(2), (2, 24, cfg.d_model))
    params = flax.core.meta.unbox(layer.init(jax.random.key(3), x)["params"])
    params = jax.tree.map(  # biases and lambdas away from their zero start
        lambda p: p + 0.1 * jax.random.normal(jax.random.key(p.size), p.shape), params)
    with jax.default_matmul_precision("highest"):
        out, (k, v) = layer.apply({"params": params}, x)
        proj = lambda n, heads: (  # noqa: E731
            x @ params[n]["kernel"] + params[n]["bias"]).reshape(2, 24, heads, 8)
        q = proj("q", 8)
        np.testing.assert_allclose(k, proj("k", 4), rtol=1e-5, atol=1e-5)
        lam0 = 0.8 - 0.6 * np.exp(-0.3 * 15)
        lam = (jnp.exp(jnp.sum(params["lambda_q1"] * params["lambda_k1"]))
               - jnp.exp(jnp.sum(params["lambda_q2"] * params["lambda_k2"])) + lam0)
        o = dense_diff_attention(q, k, v, lam, window)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
        o = o * params["subln"]["scale"] * (1 - lam0)
        want = o.reshape(2, 24, 64) @ params["out"]["kernel"] + params["out"]["bias"]
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)


# ------------------------------------------------ what travels down the stack


def test_config_says_which_layers_are_kept_and_refuses_a_reader_without_a_source():
    cfg = lm_config(small_model())
    assert [cfg.layer_is_kept(i) for i in range(5)] == [False, True, True, False, False]
    assert cfg.carries and not cfg.layers_alike and cfg.recurrent == (
        "mamba", "gmu", "cross_attention")
    assert [cfg.layer_window(i) for i in range(5)] == [8, 0, 0, 0, 0]
    assert not any(cfg.layer_rope(i) for i in range(5))
    assert cfg.layer_lam0(2) == pytest.approx(ref.lam0_of(17))
    # a second mamba layer before the gmu takes the first one's place
    two = LMConfig(n_layers=3, layer_types=("mamba", "mamba", "gmu"))
    assert [two.layer_is_kept(i) for i in range(3)] == [False, True, False]
    assert set(LAYER_KINDS) == set(ref._KINDS)
    with pytest.raises(ValueError, match="gmu layer reads what a mamba layer"):
        LMConfig(n_layers=2, layer_types=("gmu", "mamba"))
    with pytest.raises(ValueError, match="cross_attention layer reads what a full_attention"):
        LMConfig(n_layers=2, layer_types=("sliding_attention", "cross_attention"),
                 diff_attn=True)
    with pytest.raises(ValueError, match="one of"):
        LMConfig(n_layers=1, layer_types=("linear_attention",))
    plain = LMConfig()
    assert not plain.carries and plain.layers_alike and plain.recurrent == ()
    # the accessor says what is built: differential attention rotates nothing
    assert plain.layer_rope(0) and not LMConfig(diff_attn=True).layer_rope(0)
    assert cfg.ssm_rank == 2 and LMConfig(d_model=2560).ssm_rank == 160


def test_the_family_refuses_a_rank_the_program_would_not_build():
    with pytest.raises(ValueError, match="ssm_dt_rank 3 is not the program's"):
        lm_config(small_model(ssm_dt_rank=3))


@pytest.mark.parametrize("zeroed,reader", [
    ("block2/attn/k/kernel block2/attn/k/bias block2/attn/v/kernel block2/attn/v/bias", "xattn"),
    ("block1/ssm/x_proj/kernel block1/ssm/D", "gmu"),
], ids=["layer17_kv", "layer16_scan"])
def test_a_kept_layers_values_reach_the_layers_that_read_them(zeroed, reader):
    """With layer 17's K/V projection zeroed the cross layer attends
    nothing but zeros, and with layer 16's scan zeroed (no B, C or D) the
    GMU gates zeros: each reader's branch then adds its bias or nothing,
    whatever the tokens.  Unzeroed, the reader's own output moves."""
    model = small_model()
    lm, flat, params, inp, _ = program_and_weights(model)
    cut = unflatten_like(params, {
        k: jnp.zeros_like(v) if k in zeroed.split() else v for k, v in flat.items()})
    index = 4 if reader == "xattn" else 3

    def reader_output(p):
        _, col = lm.apply({"params": p}, inp, mutable=["intermediates"],
                          capture_intermediates=lambda mdl, _: mdl.name == reader)
        return col["intermediates"][f"block{index}"][reader]["__call__"][0]

    whole, silenced = reader_output(params), reader_output(cut)
    whole = whole[0] if isinstance(whole, tuple) else whole
    silenced = silenced[0] if isinstance(silenced, tuple) else silenced
    assert float(jnp.abs(whole).max()) > 1e-3
    # every position alike: the out projection's bias alone (cross), or 0
    assert float(jnp.abs(silenced - silenced[:1, :1]).max()) <= 1e-6
    if reader == "gmu":
        assert float(jnp.abs(silenced).max()) == 0.0


def test_gradients_reach_the_kept_layers_through_the_carry():
    """A reader block's gradient with respect to what it was handed is not
    zero, and in the whole model layer 16's and 17's gradients change when
    the readers' out projections are cut (the residual stream alone does
    not carry that part)."""
    cfg = lm_config(small_model())
    x = jax.random.normal(jax.random.key(0), (2, 24, 32))
    m = jax.random.normal(jax.random.key(1), (2, 24, 64))
    kv = tuple(jax.random.normal(jax.random.key(i), (2, 24, 4, 8)) for i in (2, 3))
    for layer, carry in ((3, {"ssm": m}), (4, {"kv": kv})):
        blk = Block(cfg, None, layer)
        p = blk.init(jax.random.key(4), x, None, True, carry)["params"]
        g = jax.grad(lambda c: jnp.sum(blk.apply({"params": p}, x, None, True, c)[0] ** 2))(carry)
        assert all(float(jnp.abs(leaf).max()) > 0 for leaf in jax.tree.leaves(g))
    lm, flat, params, inp, tgt = program_and_weights(small_model())

    def grads_of(p):
        return flatten(jax.grad(lambda q: token_loss(
            lm.apply({"params": q}, inp, mutable=["intermediates"])[0][0], tgt))(p))

    whole = grads_of(params)
    for cut_leaf, source in (("block3/gmu/out_proj/kernel", "block1/ssm/D"),
                             ("block4/xattn/out/kernel", "block2/attn/v/kernel")):
        cut = unflatten_like(params, {
            k: jnp.zeros_like(v) if k == cut_leaf else v for k, v in flat.items()})
        assert float(jnp.abs(whole[source] - grads_of(cut)[source]).max()) > 1e-6, source


# ------------------------------------------------------------ the tied head


def test_tied_head_is_one_leaf_and_its_gradient_is_the_sum_of_both_uses():
    model = small_model()
    lm, flat, params, inp, tgt = program_and_weights(model)
    assert "lm_head" not in params and set(params["embed"]) == {"embedding"}
    loss = lambda p, lmod: token_loss(  # noqa: E731
        lmod.apply({"params": p}, inp, mutable=["intermediates"])[0][0], tgt)
    tied = jax.grad(lambda p: loss(p, lm))(params)["embed"]["embedding"]
    import dataclasses

    untied_lm = TransformerLM(dataclasses.replace(lm.cfg, tie_embeddings=False))
    untied = dict(params, lm_head={"kernel": params["embed"]["embedding"]})
    g = jax.grad(lambda p: loss(p, untied_lm))(untied)
    np.testing.assert_allclose(
        tied, g["embed"]["embedding"] + g["lm_head"]["kernel"], rtol=1e-5, atol=1e-7)
    assert float(jnp.abs(g["lm_head"]["kernel"]).max()) > 0


# ------------------------------------------------------ the vocabulary's shares


def test_the_vocabulary_shares_add_up_to_the_uncut_model():
    """The deployment cuts the tied table 8 ways.  (a) For the same final
    hidden states the 8 shares' logits, side by side, are the uncut
    model's.  (b) A share is a smaller vocabulary: the program built with
    one share's rows, fed tokens of that share, gives the uncut model's
    logits on those tokens, restricted to the share's columns."""
    model = small_model()
    shares, rows = 8, model["vocab_size"] // 8
    lm, flat, params, _, _ = program_and_weights(model)
    uncut = lambda tok: ref.forward_logits(flat, tok, model, F32)  # noqa: E731
    tok = jax.random.randint(jax.random.key(9), (2, 24), 0, model["vocab_size"])
    with jax.default_matmul_precision("highest"):
        hidden = lm.apply({"params": params}, tok, return_hidden=True,
                          mutable=["intermediates"])[0][0].astype(jnp.float32)
        side_by_side = []
        for i in range(shares):
            cfg_i = lm_config(dict(model, vocab_size=rows))
            table = params["embed"]["embedding"][i * rows:(i + 1) * rows]
            side_by_side.append(TokenEmbed(cfg_i).apply(
                {"params": {"embedding": table}}, hidden, method=TokenEmbed.attend))
            local = jax.random.randint(jax.random.key(20 + i), (2, 24), 0, rows)
            share_params = dict(params, embed={"embedding": table})
            got, _ = TransformerLM(cfg_i).apply(
                {"params": share_params}, local, mutable=["intermediates"])[0]
            want = uncut(local + i * rows)[..., i * rows:(i + 1) * rows]
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        jnp.concatenate(side_by_side, -1), uncut(tok), rtol=1e-4, atol=1e-5)


# ------------------------------------------------------- spans and counters


def test_parts_table_of_a_compiled_step_names_the_mixers(tmp_path):
    """The step's second scope table tags the Mamba layer (``ssm``, and
    inside it ``ssm/scan``), the GMU, the self layers (``attn``) and the
    cross layer (``xattn``) apart, beside ``mlp``, ``head`` and ``other``;
    the step returns the scans' largest boundary state."""
    from ddl_tpu.obs import hbm
    from ddl_tpu.obs.events import EventWriter
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import STEP_PARTS, make_lm_step_fns
    from ddl_tpu.train.state import build_optimizer

    cfg = lm_config(small_model())
    fns = make_lm_step_fns(cfg, LMMeshSpec(), build_optimizer(3e-4), jax.random.key(0), 2, 24)
    state, tok = fns.init_state(), jnp.zeros((2, 24), jnp.int32)
    writer = EventWriter(str(tmp_path), "parts", host=0)
    hbm.plan_program(writer, "train_step", fns.train, (state, tok, tok), parts=STEP_PARTS)
    parts, direction = hbm.scope_table("train_step.parts"), hbm.scope_table("train_step")
    assert set(parts) == set(direction)
    assert set(parts.values()) == {"ssm", "ssm/scan", "gmu", "attn", "xattn", "mlp", "head",
                                   "other"}
    _, m = fns.train(state, tok, tok)
    assert float(m["ssm_state_absmax"]) >= 0.0 and "moe_local_rows" not in m


def test_the_period_event_carries_the_state_s_peak_and_its_growth_is_an_anomaly(tmp_path):
    from ddl_tpu.obs import EventWriter, StepTrace, read_events

    w = EventWriter(tmp_path, "job", host=0)
    trace = StepTrace(w)
    # steady within a quarter, then three times the trailing mean; a
    # period of a program without Mamba layers feeds the rule nothing
    peaks = [4.0, 4.4, 3.8, 4.2, 4.9, 4.1, None, 12.6]
    for i, peak in enumerate(peaks):
        trace.begin_period()
        trace.end_period(i, 5 * i, elapsed=1.0, steps=5, metrics={
            "loss": 9.5, **({} if peak is None else {"ssm_state_absmax": peak})})
    w.close()
    events = read_events(w.path)
    periods = [e for e in events if e["kind"] == "period"]
    assert [e.get("ssm_state_absmax") for e in periods] == peaks
    (grown,) = [e for e in events if e["kind"] == "anomaly"]
    assert (grown["type"], grown["step"], grown["value"]) == ("ssm_state_growth", 35, 12.6)
    assert grown["baseline"] == pytest.approx(4.2333, abs=1e-3)
    assert grown["threshold"] == pytest.approx(2 * grown["baseline"])


def test_a_scan_kernel_takes_the_scan_s_part_and_its_own_direction_tag():
    from ddl_tpu.obs import scope
    from ddl_tpu.train.lm_steps import STEP_PARTS

    text = """HloModule jit_step, entry_computation_layout={()->f32[]}

ENTRY %main () -> f32[] {
  %p = f32[8,8]{1,0} parameter(0)
  %scan.1 = f32[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(LM)/block1/ssm/scan/jit(_scan_fwd)/ssm_scan_fwd/pallas_call"}
  %scan.2 = f32[8,8]{1,0} custom-call(%scan.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(LM))/block1/ssm/scan/jit(_scan_bwd)/ssm_scan_bwd/pallas_call"}
  %f.3 = f32[8,8]{1,0} fusion(%scan.2), kind=kLoop, metadata={op_name="jit(step)/jvp(LM)/block1/ssm/in_x/dot_general"}
  %f.4 = f32[8,8]{1,0} fusion(%f.3), kind=kLoop, metadata={op_name="jit(step)/jvp(LM)/block3/gmu/out_proj/dot_general"}
  ROOT %f.5 = f32[] fusion(%f.4), kind=kLoop, metadata={op_name="jit(step)/jvp(LM)/block4/xattn/flash_fwd/pallas_call"}
}
"""
    assert scope.parts_table(text, STEP_PARTS) == {
        "scan.1": "ssm/scan", "scan.2": "ssm/scan", "f.3": "ssm", "f.4": "gmu", "f.5": "xattn"}
    direction = scope.scope_table(text)
    assert direction["scan.1"] == "kernel/ssm_scan_fwd"
    assert direction["scan.2"] == "kernel/ssm_scan_bwd"


def test_obs_hbm_prints_a_scan_kernel_s_grid_and_chunk(tmp_path):
    from ddl_tpu.obs.events import EventWriter
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.obs.hbm import account_from_fold, render_hbm

    w = EventWriter(tmp_path, "plan", host=0)
    w.emit("hbm_plan", label="train_step", analysis="memory_analysis", argument_bytes=4096,
           output_bytes=4096, temp_bytes=512, alias_bytes=0, code_bytes=64,
           kernel_tiles={"ssm_scan_fwd": {"calls": 1, "total": 320, "steps": 40960}})
    w.emit("hbm_sample", params_bytes=600, watermark=2000, peak=2000, limit=4096, synthetic=True)
    w.close()
    out = render_hbm(account_from_fold(fold_job(tmp_path, "plan", cache=False)), "plan")
    assert "tiles ssm_scan_fwd: 1 call(s), 320 grid steps of 128 time steps" in out


# ------------------------------------------------------------- what refuses it


def test_serving_and_the_pipeline_refuse_the_stack_and_say_why():
    from ddl_tpu.infer.decode import make_lm_generator
    from ddl_tpu.parallel.lm_pipeline import make_lm_pipeline_step_fns
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.serve.engine import make_serve_step_fns
    from ddl_tpu.train.state import build_optimizer

    cfg = lm_config(small_model())
    said = "trains only: its mamba, gmu, cross_attention layers.*ROADMAP R4"
    with pytest.raises(NotImplementedError, match=said):
        refuse_cache_over_layer_types(cfg)
    with pytest.raises(NotImplementedError, match=said):
        make_serve_step_fns(cfg, block_size=8, num_blocks=8, max_batch=2)
    with pytest.raises(NotImplementedError, match=said):
        make_lm_generator(cfg, prompt_len=8, max_new=4)
    four = lm_config(small_model(n_layers=4, layer_types=KINDS[1:],
                                 layer_indices=[16, 17, 18, 19]))
    with pytest.raises(NotImplementedError, match="stacks one block"):
        make_lm_pipeline_step_fns(
            four, LMMeshSpec(pipe=2), build_optimizer(3e-4), jax.random.key(0), 2, 24, 2)
    with pytest.raises(NotImplementedError, match="tie_embeddings"):
        make_lm_pipeline_step_fns(
            LMConfig(n_layers=2, tie_embeddings=True), LMMeshSpec(pipe=2),
            build_optimizer(3e-4), jax.random.key(0), 2, 24, 2)
    with pytest.raises(ValueError, match="chunked loss edge"):
        LMConfig(tie_embeddings=True, ce_chunk=8)
