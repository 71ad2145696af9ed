"""Parallelism correctness: DP/PP/hybrid vs exact sequential references.

The reference validates its parallelism only empirically — metric parity of
final-epoch stats across strategies, averaged over 10 cluster runs
(``ipynb/main.ipynb`` cell 5; SURVEY.md section 4).  Here every strategy is
checked *numerically* against a from-scratch sequential implementation on a
simulated 8-device CPU mesh: one optimizer step must produce (near-)identical
parameters, loss, and predictions.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl_tpu.config import TrainConfig
from ddl_tpu.models import apply_stage, build_stages, stage_boundary_shapes
from ddl_tpu.ops import softmax_cross_entropy
from ddl_tpu.parallel.mesh import MeshSpec, build_mesh
from ddl_tpu.parallel.pipeline import make_pipeline_step_fns
from ddl_tpu.train.state import create_train_state, make_optimizer
from ddl_tpu.train.steps import make_dp_step_fns

IMG = 16
B = 8
NUM_CLASSES = 5


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    images = rng.integers(0, 255, (B, IMG, IMG, 3)).astype(np.uint8)
    labels = rng.integers(0, NUM_CLASSES, (B,)).astype(np.int32)
    return images, labels


def _fresh(tiny_model_cfg, num_stages=None, sgd=False):
    stages = build_stages(tiny_model_cfg, num_stages=num_stages)
    # Parity tests compare post-update params with SGD: Adam's first step is
    # +-lr * sign(grad), which amplifies reduction-order fp noise on
    # near-zero grads into full-lr sign flips.  SGD keeps the comparison
    # proportional to the (tiny) gradient difference.
    tx = optax.sgd(0.1) if sgd else make_optimizer(TrainConfig())
    state = create_train_state(stages, tx, jax.random.key(0), IMG)
    return stages, tx, state


def _clone(state):
    return jax.tree.map(jnp.copy, state)


def microbatch_loss_and_grads(stages, params, stats, images, labels):
    """One microbatch through every stage on one device: its mean CE, the
    gradient of that, the logits and the stages' new BN statistics.  Pure
    jax.numpy, no mesh — a caller at real widths wraps it in ``jax.jit``
    (``chip_smoke.py --chips 4``), where op-by-op dispatch of a whole
    DenseNet121 would take minutes."""

    def loss_fn(params):
        x = images.astype(jnp.float32) / 255.0
        new_stats = []
        for i, st in enumerate(stages):
            x, ns = apply_stage(st, params[i], stats[i], x, train=True)
            new_stats.append(ns)
        return softmax_cross_entropy(x, labels).mean(), (x, tuple(new_stats))

    (loss, (logits, new_stats)), grads = jax.value_and_grad(
        loss_fn, has_aux=True
    )(params)
    return loss, grads, logits, new_stats


def sequential_reference_step(stages, tx, state, images, labels, M, D,
                              micro=None):
    """Ground truth: loop over D data shards x M microbatches, gradient of
    the averaged loss (the average of the microbatch gradients), single
    optimizer update — no mesh.  ``micro(params, stats, images, labels)``
    defaults to ``microbatch_loss_and_grads`` over ``stages``."""
    if micro is None:
        micro = partial(microbatch_loss_and_grads, stages)
    shard = images.shape[0] // D
    mb = shard // M
    loss, grads, logits_cat, shard_stats = 0.0, None, [], []
    for d in range(D):
        stats = state.batch_stats
        for m in range(M):
            lo = d * shard + m * mb
            loss_dm, g, logits, stats = micro(
                state.params, stats, images[lo : lo + mb], labels[lo : lo + mb]
            )
            loss = loss + loss_dm / (M * D)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            logits_cat.append(logits)
        shard_stats.append(stats)
    grads = jax.tree.map(lambda g: g / (M * D), grads)
    updates, _ = tx.update(grads, state.opt_state, state.params)
    new_params = optax.apply_updates(state.params, updates)
    mean_stats = jax.tree.map(lambda *xs: jnp.mean(jnp.stack(xs), 0), *shard_stats)
    preds = np.argmax(np.asarray(jnp.concatenate(logits_cat)), -1)
    return new_params, mean_stats, float(loss), preds


def _assert_tree_close(a, b, atol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol, rtol=1e-4)


def test_dp_matches_single(tiny_model_cfg, batch):
    """DP over ('data',) is bit-compatible with single-device on the same
    global batch — same jit program, just sharded (SyncBN semantics)."""
    images, labels = batch
    stages, tx, state0 = _fresh(tiny_model_cfg, num_stages=1, sgd=True)

    single = make_dp_step_fns(stages, tx, build_mesh(MeshSpec(1, 1)), jnp.float32)
    dp = make_dp_step_fns(stages, tx, build_mesh(MeshSpec(4, 1)), jnp.float32)

    s1, loss1, pred1 = single.train(_clone(state0), images, labels)
    s2, loss2, pred2 = dp.train(_clone(state0), images, labels)
    assert float(loss1) == pytest.approx(float(loss2), abs=1e-5)
    np.testing.assert_array_equal(np.asarray(pred1), np.asarray(pred2))
    _assert_tree_close(s1.params, s2.params, atol=1e-5)
    _assert_tree_close(s1.batch_stats, s2.batch_stats, atol=1e-5)


@pytest.mark.parametrize("data,microbatches", [(1, 2), (1, 4), (2, 2), (4, 2)])
def test_pipeline_matches_sequential(tiny_model_cfg, batch, data, microbatches):
    """GPipe schedule (+ optional DP axis) == sequential microbatched math."""
    images, labels = batch
    stages, tx, state0 = _fresh(tiny_model_cfg, sgd=True)
    mesh = build_mesh(MeshSpec(data, 2))
    fns = make_pipeline_step_fns(
        stages,
        tx,
        mesh,
        jnp.float32,
        num_microbatches=microbatches,
        boundary_shapes=stage_boundary_shapes(tiny_model_cfg, IMG),
        num_classes=NUM_CLASSES,
        remat=False,
    )
    new_state, loss, preds = fns.train(_clone(state0), images, labels)
    ref_params, ref_stats, ref_loss, ref_preds = sequential_reference_step(
        stages, tx, _clone(state0), images, labels, M=microbatches, D=data
    )
    assert float(loss) == pytest.approx(ref_loss, abs=1e-5)
    np.testing.assert_array_equal(np.asarray(preds), ref_preds)
    _assert_tree_close(new_state.params, ref_params, atol=2e-5)
    _assert_tree_close(new_state.batch_stats, tuple(ref_stats), atol=2e-5)


@pytest.mark.parametrize("data,microbatches", [(1, 2), (2, 2), (1, 4)])
def test_1f1b_matches_gpipe(tiny_model_cfg, batch, data, microbatches):
    """The hand-written 1F1B interleave must reproduce the autodiff-derived
    GPipe schedule exactly — same microbatch math, different clocking."""
    images, labels = batch
    stages, tx, state0 = _fresh(tiny_model_cfg, sgd=True)
    mesh = build_mesh(MeshSpec(data, 2))
    kwargs = dict(
        tx=tx,
        mesh=mesh,
        compute_dtype=jnp.float32,
        num_microbatches=microbatches,
        boundary_shapes=stage_boundary_shapes(tiny_model_cfg, IMG),
        num_classes=NUM_CLASSES,
        remat=False,
    )
    g = make_pipeline_step_fns(stages, schedule="gpipe", **kwargs)
    f = make_pipeline_step_fns(stages, schedule="1f1b", **kwargs)
    sg, lg, pg = g.train(_clone(state0), images, labels)
    sf, lf, pf = f.train(_clone(state0), images, labels)
    assert float(lg) == pytest.approx(float(lf), abs=1e-6)
    np.testing.assert_array_equal(np.asarray(pg), np.asarray(pf))
    _assert_tree_close(sg.params, sf.params, atol=1e-6)
    _assert_tree_close(sg.batch_stats, sf.batch_stats, atol=1e-6)


def test_pipeline_remat_matches_no_remat(tiny_model_cfg, batch):
    """jax.checkpoint on stages must not change the math."""
    images, labels = batch
    stages, tx, state0 = _fresh(tiny_model_cfg, sgd=True)
    mesh = build_mesh(MeshSpec(1, 2))
    kwargs = dict(
        tx=tx,
        mesh=mesh,
        compute_dtype=jnp.float32,
        num_microbatches=2,
        boundary_shapes=stage_boundary_shapes(tiny_model_cfg, IMG),
        num_classes=NUM_CLASSES,
    )
    a = make_pipeline_step_fns(stages, remat=False, **kwargs)
    b = make_pipeline_step_fns(stages, remat=True, **kwargs)
    sa, la, _ = a.train(_clone(state0), images, labels)
    sb, lb, _ = b.train(_clone(state0), images, labels)
    assert float(la) == pytest.approx(float(lb), abs=1e-6)
    _assert_tree_close(sa.params, sb.params, atol=1e-6)


def test_pipeline_eval_matches_sequential_eval(tiny_model_cfg, batch):
    images, _ = batch
    stages, tx, state0 = _fresh(tiny_model_cfg)
    mesh = build_mesh(MeshSpec(2, 2))
    fns = make_pipeline_step_fns(
        stages,
        tx,
        mesh,
        jnp.float32,
        num_microbatches=2,
        boundary_shapes=stage_boundary_shapes(tiny_model_cfg, IMG),
        num_classes=NUM_CLASSES,
        remat=False,
    )
    logits = np.asarray(fns.evaluate(_clone(state0), images))
    x = images.astype(jnp.float32) / 255.0
    for i, st in enumerate(stages):
        x, _ = apply_stage(st, state0.params[i], state0.batch_stats[i], x, train=False)
    np.testing.assert_allclose(logits, np.asarray(x), atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("spec", [MeshSpec(1, 1), MeshSpec(4, 1), MeshSpec(1, 2), MeshSpec(2, 2)])
def test_strategies_learn(tiny_model_cfg, spec):
    """Loss must descend on learnable synthetic data under every strategy
    (replaces the reference's strategy-vs-single metric-parity check)."""
    from ddl_tpu.data import SyntheticAptosDataset

    ds = SyntheticAptosDataset(B * 8, image_size=IMG, seed=0, noise=0.05)
    pipelined = spec.pipe > 1
    stages, tx, state = _fresh(tiny_model_cfg, num_stages=None if pipelined else 1)
    mesh = build_mesh(spec)
    if pipelined:
        fns = make_pipeline_step_fns(
            stages,
            tx,
            mesh,
            jnp.float32,
            num_microbatches=2,
            boundary_shapes=stage_boundary_shapes(tiny_model_cfg, IMG),
            num_classes=NUM_CLASSES,
            remat=False,
        )
    else:
        fns = make_dp_step_fns(stages, tx, mesh, jnp.float32)
    losses = []
    for step in range(20):
        idx = np.arange(B) + (step % 8) * B
        images = np.stack([ds[i][0] for i in idx])
        labels = np.asarray([ds[i][1] for i in idx], np.int32)
        state, loss, _ = fns.train(state, images, labels)
        losses.append(float(loss))
    assert np.mean(losses[-4:]) < np.mean(losses[:4]) * 0.9, losses
