"""DenseNet structure tests: parity with torchvision densenet121 shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.config import ModelConfig
from ddl_tpu.models import (
    build_stages,
    count_params,
    forward_stages,
    init_stages,
    stage_boundary_shapes,
)

# torchvision densenet121 with a 5-class head (reference single.py:297-299):
# features 6,953,856 params + classifier 1024*5+5.
DENSENET121_5CLASS_PARAMS = 6_958_981


@pytest.fixture(scope="module")
def full_cfg():
    return ModelConfig()


def _abstract_param_counts(cfg, num_stages=None, image_size=224):
    """Per-stage param counts via eval_shape (no FLOPs, fast on CPU)."""
    stages = build_stages(cfg, num_stages=num_stages)
    counts = []
    x = jax.ShapeDtypeStruct((1, image_size, image_size, 3), jnp.float32)
    for stage in stages:
        variables = jax.eval_shape(
            lambda k, v, s=stage: s.init(k, v, train=False), jax.random.key(0), x
        )
        counts.append(count_params(variables["params"]))
        x = jax.eval_shape(lambda v, y, s=stage: s.apply(v, y, train=False), variables, x)
    return counts


def test_param_count_matches_torchvision(full_cfg):
    assert sum(_abstract_param_counts(full_cfg, num_stages=1)) == DENSENET121_5CLASS_PARAMS


def test_staged_split_param_counts(full_cfg):
    """The 2-stage split must partition the exact same parameters."""
    s0, s1 = _abstract_param_counts(full_cfg)
    assert s0 + s1 == DENSENET121_5CLASS_PARAMS
    # the reference split is unbalanced toward the later blocks (debug.py
    # prints per-stage counts); sanity-check the imbalance direction.
    assert 0 < s0 < s1


def test_boundary_shape(full_cfg):
    # split at denseblock3 start: activation entering block3 is 14x14x256 for
    # 224x224 inputs (stem /4 -> 56, transition1 -> 28, transition2 -> 14).
    assert stage_boundary_shapes(full_cfg, 224) == [(14, 14, 256)]


def test_forward_shapes_and_dtype(tiny_model_cfg):
    stages = build_stages(tiny_model_cfg)
    params, batch_stats = init_stages(stages, jax.random.key(0), image_size=16)
    x = jnp.ones((2, 16, 16, 3), jnp.float32)
    logits, new_stats = forward_stages(stages, params, batch_stats, x, train=True)
    assert logits.shape == (2, 5)
    assert logits.dtype == jnp.float32
    # batch_stats must actually update in train mode
    old = jax.tree_util.tree_leaves(batch_stats)
    new = jax.tree_util.tree_leaves(new_stats)
    assert any(not np.allclose(a, b) for a, b in zip(old, new))
    # eval mode leaves them untouched
    _, same_stats = forward_stages(stages, params, batch_stats, x, train=False)
    for a, b in zip(jax.tree_util.tree_leaves(batch_stats), jax.tree_util.tree_leaves(same_stats)):
        np.testing.assert_array_equal(a, b)


def test_single_vs_staged_forward_identical(tiny_model_cfg):
    """Splitting into stages must not change the math."""
    stages2 = build_stages(tiny_model_cfg)
    stages1 = build_stages(tiny_model_cfg, num_stages=1)
    p2, s2 = init_stages(stages2, jax.random.key(0), image_size=16)
    x = jax.random.normal(jax.random.key(1), (3, 16, 16, 3))

    # Rebuild the single-stage params from the 2-stage params: the module
    # names are disjoint (blocks keep their global indices), so merging the
    # dicts gives the exact single-stage tree.
    merged_params = {**p2[0], **p2[1]}
    merged_stats = {**s2[0], **s2[1]}
    out2, _ = forward_stages(stages2, p2, s2, x, train=False)
    out1, _ = forward_stages(stages1, (merged_params,), (merged_stats,), x, train=False)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6, atol=1e-6)


def test_bad_split_rejected(tiny_model_cfg):
    import dataclasses

    cfg = dataclasses.replace(tiny_model_cfg, split_blocks=(0,))
    with pytest.raises(ValueError):
        build_stages(cfg)


@pytest.mark.parametrize("alt_impl", ["packed"])
def test_alt_block_impl_matches_concat(tiny_model_cfg, alt_impl):
    """dense_block_impl='packed' (lane-aligned packs, implicit concat via
    per-pack 1x1 contraction, stats-once) is the same math as the
    textbook concat form: identical params, forward, train-mode batch
    stats, and gradients."""
    import dataclasses

    x = jax.random.normal(jax.random.key(2), (2, 16, 16, 3))
    outs = {}
    for impl in ("concat", alt_impl):
        cfg = dataclasses.replace(tiny_model_cfg, dense_block_impl=impl)
        stages = build_stages(cfg, num_stages=1)
        params, bstats = init_stages(stages, jax.random.key(0), image_size=16)

        def loss(params, bstats, x):
            logits, ns = forward_stages(stages, params, bstats, x, train=True)
            return (logits ** 2).sum(), ns

        (val, ns), grads = jax.value_and_grad(loss, has_aux=True)(
            params, bstats, x
        )
        outs[impl] = (val, ns, grads, params)
    # same init (param tree is impl-independent)
    ca, cb = jax.tree.structure(outs["concat"][3]), jax.tree.structure(outs[alt_impl][3])
    assert ca == cb
    for a, b in zip(jax.tree.leaves(outs["concat"][3]), jax.tree.leaves(outs[alt_impl][3])):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(outs["concat"][0], outs[alt_impl][0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(outs["concat"][1]), jax.tree.leaves(outs[alt_impl][1])):
        np.testing.assert_allclose(a, b, atol=1e-5)
    for a, b in zip(jax.tree.leaves(outs["concat"][2]), jax.tree.leaves(outs[alt_impl][2])):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_packed_bf16_close_to_concat(tiny_model_cfg, monkeypatch):
    """In bf16 compute the packed block accumulates cross-pack partial
    sums in bf16 (deliberate: a bf16 partial write is half the HBM
    traffic; each pack's own contraction still accumulates f32 in the
    MXU), diverging from the concat form's single f32-accumulated
    matmul.  Pin the drift: multi-pack bf16 forward within bf16-level
    tolerance of the concat form."""
    import dataclasses

    from ddl_tpu.models import densenet as dn

    monkeypatch.setattr(dn, "_PACK", 8)  # force several packs
    x = jax.random.normal(jax.random.key(2), (2, 16, 16, 3))
    outs = {}
    for impl in ("concat", "packed"):
        cfg = dataclasses.replace(
            tiny_model_cfg, dense_block_impl=impl, compute_dtype="bfloat16"
        )
        stages = build_stages(cfg, num_stages=1)
        params, bstats = init_stages(stages, jax.random.key(0), image_size=16)
        logits, _ = forward_stages(stages, params, bstats, x, train=True)
        outs[impl] = np.asarray(logits, np.float32)
    # bf16 has ~3 decimal digits; cross-pack reassociation costs at most
    # a few ulps on top
    np.testing.assert_allclose(
        outs["concat"], outs["packed"], atol=0.05, rtol=0.02
    )


def test_packed_multi_pack_and_eval(tiny_model_cfg, monkeypatch):
    """The packed impl with features spanning MULTIPLE lane packs (pack
    width patched to 8 so the tiny config splits/merges/slices across
    packs), in both train and eval mode (eval reads each consumer's own
    running stats, sliced per pack)."""
    import dataclasses

    from ddl_tpu.models import densenet

    monkeypatch.setattr(densenet, "_PACK", 8)
    x = jax.random.normal(jax.random.key(3), (2, 16, 16, 3))
    outs = {}
    for impl in ("concat", "packed"):
        cfg = dataclasses.replace(tiny_model_cfg, dense_block_impl=impl)
        stages = build_stages(cfg, num_stages=1)
        params, bstats = init_stages(stages, jax.random.key(0), image_size=16)
        # one train step to make running stats non-trivial before eval
        logits_tr, ns = forward_stages(stages, params, bstats, x, train=True)
        logits_ev, _ = forward_stages(stages, params, ns, x, train=False)
        outs[impl] = (logits_tr, ns, logits_ev)
    np.testing.assert_allclose(
        np.asarray(outs["concat"][0]), np.asarray(outs["packed"][0]),
        atol=1e-5,
    )
    for a, b in zip(
        jax.tree.leaves(outs["concat"][1]), jax.tree.leaves(outs["packed"][1])
    ):
        np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(outs["concat"][2]), np.asarray(outs["packed"][2]),
        atol=1e-5,
    )


def test_packed_staged_matches_single(tiny_model_cfg):
    """The packed impl through the PIPELINE staging path (stage boundary
    falls between blocks, where the packed transition hands a dense
    tensor across) equals its single-stage forward."""
    import dataclasses

    cfg = dataclasses.replace(tiny_model_cfg, dense_block_impl="packed")
    stages2 = build_stages(cfg)
    stages1 = build_stages(cfg, num_stages=1)
    p2, s2 = init_stages(stages2, jax.random.key(0), image_size=16)
    x = jax.random.normal(jax.random.key(1), (3, 16, 16, 3))
    merged_params = {**p2[0], **p2[1]}
    merged_stats = {**s2[0], **s2[1]}
    out2, _ = forward_stages(stages2, p2, s2, x, train=True)
    out1, _ = forward_stages(
        stages1, (merged_params,), (merged_stats,), x, train=True
    )
    np.testing.assert_allclose(
        np.asarray(out1), np.asarray(out2), rtol=1e-6, atol=1e-6
    )
