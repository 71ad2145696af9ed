"""Partition-rule engine (parallel/rules.py): matching semantics
(first-match-wins, search-anywhere, anchoring, scalar fallthrough,
unmatched-leaf error), per-family tables resolving every real parameter
path identically to the legacy logical-axis resolution, the ZeRO shard
derivation, the optimizer-HBM accounting, and the rule-driven
shard/gather pair.
"""

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ddl_tpu.parallel import rules as R

# ---------------------------------------------------------------------------
# matching semantics
# ---------------------------------------------------------------------------


def _leaf(shape):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.float32)


def test_first_match_wins_precedence():
    rules = (
        (r"attn/q/kernel$", P(None, "model")),
        (r"kernel$", P("model", None)),  # broader rule later
    )
    tree = {"attn": {"q": {"kernel": _leaf((8, 8))},
                     "out": {"kernel": _leaf((8, 8))}}}
    specs = R.match_partition_rules(rules, tree)
    assert specs["attn"]["q"]["kernel"] == P(None, "model")
    assert specs["attn"]["out"]["kernel"] == P("model", None)
    # reversed order: the broad rule shadows the specific one
    specs2 = R.match_partition_rules(tuple(reversed(rules)), tree)
    assert specs2["attn"]["q"]["kernel"] == P("model", None)


def test_search_matches_anywhere_and_anchor_pins_end():
    rules = ((r"mlp/wi/kernel$", P(None, "model")),)
    # the pattern matches mid-path (optimizer moments embed param paths)
    tree = {"0": {"mu": {"block0": {"mlp": {"wi": {"kernel": _leaf((8, 32))}}}}}}
    specs = R.match_partition_rules(rules, tree)
    assert jtu.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))[0] == P(None, "model")
    # the $ anchor refuses a path that merely CONTAINS the name
    with pytest.raises(R.UnmatchedLeafError):
        R.match_partition_rules(
            rules, {"mlp": {"wi": {"kernel_scale": _leaf((8, 32))}}}
        )


def test_scalars_and_single_elements_replicate_without_rules():
    specs = R.match_partition_rules(
        (), {"count": _leaf(()), "one": _leaf((1,))}
    )
    assert specs == {"count": P(), "one": P()}


def test_unmatched_leaf_error_names_paths_and_strict_false_replicates():
    tree = {"mystery": {"kernel": _leaf((16, 16))}}
    with pytest.raises(R.UnmatchedLeafError) as ei:
        R.match_partition_rules((), tree, strict=True)
    assert "mystery/kernel" in str(ei.value)
    assert R.match_partition_rules((), tree, strict=False) == {
        "mystery": {"kernel": P()}
    }


def test_provenance_distinguishes_explicit_replication():
    rules = ((r"pos_embed$", P()), (r"kernel$", P(None, "model")))
    tree = {"pos_embed": _leaf((1, 4, 64)), "q": {"kernel": _leaf((8, 8))}}
    prov = {name: (spec, pat)
            for name, _l, spec, pat in R.match_with_provenance(rules, tree)}
    assert prov["pos_embed"] == (P(), r"pos_embed$")
    assert prov["q/kernel"] == (P(None, "model"), r"kernel$")


# ---------------------------------------------------------------------------
# family tables vs the legacy logical-axis resolution
# ---------------------------------------------------------------------------


def _lm_mesh():
    from ddl_tpu.parallel.sharding import LMMeshSpec, build_lm_mesh

    return build_lm_mesh(LMMeshSpec(data=2, model=2, expert=2))


def _assert_table_matches_logical(abs_params, table, fsdp, mesh):
    import flax.linen as nn

    from ddl_tpu.parallel.sharding import lm_logical_rules

    logical = nn.get_partition_spec(abs_params)
    legacy = nn.logical_to_mesh_sharding(logical, mesh, lm_logical_rules(fsdp))
    unboxed = nn.meta.unbox(abs_params)
    ours = table.shardings(unboxed, mesh)
    for (path, leaf), (_, l), (_, o) in zip(
        jtu.tree_leaves_with_path(unboxed),
        jtu.tree_leaves_with_path(legacy),
        jtu.tree_leaves_with_path(ours),
    ):
        assert l.is_equivalent_to(o, len(leaf.shape)), (
            f"{R.tree_path_str(path)}: legacy {l.spec} != table {o.spec}"
        )


@pytest.mark.parametrize("fsdp", [False, True])
@pytest.mark.parametrize("moe", [0, 2])
def test_lm_table_matches_logical_resolution(fsdp, moe):
    from ddl_tpu.models.transformer import LMConfig, TransformerLM

    cfg = LMConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, head_dim=16,
        d_ff=256, compute_dtype="float32", num_experts=moe, fsdp=fsdp,
    )
    abs_params = jax.eval_shape(
        lambda r: TransformerLM(cfg, None).init(
            r, jnp.zeros((4, 8), jnp.int32)
        )["params"],
        jax.random.key(0),
    )
    _assert_table_matches_logical(abs_params, R.lm_rules(fsdp), fsdp, _lm_mesh())


@pytest.mark.parametrize("fsdp", [False, True])
def test_afmoe_table_matches_logical_resolution(fsdp):
    """The gated block's leaves (attention gate, q/k norms, the three
    expert banks, the shared expert, the selection bias) each have a
    rule, and it is the one their logical annotations resolve to: a
    four-chip cell does not start from an unsharded bank of experts."""
    import flax.linen as nn

    from ddl_tpu.models.transformer import LMConfig, TransformerLM

    cfg = LMConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=192, compute_dtype="float32", fsdp=fsdp,
        num_experts=8, expert_top_k=2, num_dense_layers=1,
        moe_router="sigmoid", moe_d_ff=32, num_shared_experts=1,
        layer_types=("sliding_attention", "full_attention"), attn_window=4,
        qk_norm=True, attn_gate=True, mlp_gated=True,
        sandwich_norm=True, embed_scale=True, expert_share=(1, 2),
    )
    abs_params = jax.eval_shape(
        lambda r: TransformerLM(cfg, None).init(
            r, jnp.zeros((4, 8), jnp.int32)
        )["params"],
        jax.random.key(0),
    )
    names = {R.tree_path_str(p) for p, _ in
             jtu.tree_leaves_with_path(nn.meta.unbox(abs_params))}
    for leaf in ("block0/attn/gate/kernel", "block0/attn/q_norm/scale",
                 "block0/mlp/wg/kernel", "block1/moe/wg", "block1/moe/bias",
                 "block1/moe/shared/wo/kernel", "block1/norm_post_mlp/scale"):
        assert leaf in names
    _assert_table_matches_logical(abs_params, R.lm_rules(fsdp), fsdp, _lm_mesh())


@pytest.mark.parametrize("fsdp", [False, True])
def test_sambay_table_matches_logical_resolution(fsdp):
    """The hybrid stack's leaves (the Mamba layer's projections,
    convolution, step bias, A and D; the GMU's two matrices; differential
    and cross attention's biases, lambda vectors and pair norm; LayerNorm's
    bias; a tied embedding with no lm_head) each have a rule, and it is
    the one their logical annotations resolve to: d_inner splits over
    'model' as an MLP's hidden width does."""
    import flax.linen as nn

    from ddl_tpu.models.transformer import LMConfig, TransformerLM

    cfg = LMConfig(
        vocab_size=512, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=192, compute_dtype="float32", fsdp=fsdp,
        layer_types=("sliding_attention", "mamba", "full_attention", "gmu",
                     "cross_attention"),
        attn_window=4, norm="layer", tie_embeddings=True,
        diff_attn=True, mlp_gated=True, ssm_state=4,
    )
    abs_params = jax.eval_shape(
        lambda r: TransformerLM(cfg, None).init(
            r, jnp.zeros((4, 8), jnp.int32)
        )["params"],
        jax.random.key(0),
    )
    names = {R.tree_path_str(p) for p, _ in
             jtu.tree_leaves_with_path(nn.meta.unbox(abs_params))}
    for leaf in ("block0/attn/q/bias", "block0/attn/lambda_q1", "block0/attn/subln/scale",
                 "block0/norm_attn/bias", "block1/ssm/in_x/kernel", "block1/ssm/conv/kernel",
                 "block1/ssm/conv/bias", "block1/ssm/x_proj/kernel",
                 "block1/ssm/dt_proj/bias", "block1/ssm/A_log", "block1/ssm/D",
                 "block1/ssm/out_proj/kernel", "block3/gmu/in_proj/kernel",
                 "block3/gmu/out_proj/kernel", "block4/xattn/q/kernel",
                 "block4/xattn/out/bias", "norm_f/bias"):
        assert leaf in names
    assert "lm_head" not in {n.split("/")[0] for n in names}
    assert not any(n.startswith("block4/xattn/k") for n in names)
    _assert_table_matches_logical(abs_params, R.lm_rules(fsdp), fsdp, _lm_mesh())


@pytest.mark.parametrize("fsdp", [False, True])
def test_vit_table_matches_logical_resolution(fsdp):
    from ddl_tpu.models.vit import ViT, ViTConfig

    cfg = ViTConfig(
        image_size=16, patch_size=8, d_model=64, n_layers=2, n_heads=4,
        head_dim=16, d_ff=256, compute_dtype="float32", remat=False,
        fsdp=fsdp,
    )
    abs_params = jax.eval_shape(
        lambda r: ViT(cfg).init(
            r, jnp.zeros((2, 16, 16, 3), jnp.float32)
        )["params"],
        jax.random.key(0),
    )
    _assert_table_matches_logical(abs_params, R.vit_rules(fsdp), fsdp, _lm_mesh())


def test_gqa_lm_paths_resolve():
    """Grouped-query configs change K/V shapes, not names — the table
    must still cover every leaf."""
    from ddl_tpu.models.transformer import LMConfig, TransformerLM

    import flax.linen as nn

    cfg = LMConfig(
        vocab_size=128, d_model=64, n_layers=1, n_heads=4, head_dim=16,
        d_ff=128, compute_dtype="float32", n_kv_heads=2,
    )
    abs_params = nn.meta.unbox(jax.eval_shape(
        lambda r: TransformerLM(cfg, None).init(
            r, jnp.zeros((2, 8), jnp.int32)
        )["params"],
        jax.random.key(0),
    ))
    R.lm_rules().specs(abs_params)  # strict: raises on any gap


def test_cnn_table_covers_densenet_and_decode_table_is_lm():
    from ddl_tpu.config import ModelConfig
    from ddl_tpu.models import build_stages
    from ddl_tpu.models.densenet import init_stages

    cfg = ModelConfig(
        growth_rate=4, block_config=(2, 2), num_init_features=8, bn_size=2,
        num_classes=5, split_blocks=(1,), compute_dtype="float32",
        remat=False,
    )
    stages = build_stages(cfg, num_stages=1)
    params = jax.eval_shape(
        lambda r: init_stages(stages, r, 16)[0], jax.random.key(0)
    )
    specs = R.cnn_rules().specs(params)
    assert all(
        s == P() for s in jtu.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    )
    assert R.cnn_rules().contract()["replicated_params_ok"] is True
    d = R.decode_rules()
    assert d.rules == R.lm_rules().rules
    assert d.contract()["donate_state"] is False
    assert d.in_specs["prompt"] == R.DECODE_TOKEN_SPEC


# ---------------------------------------------------------------------------
# ZeRO derivation + HBM accounting
# ---------------------------------------------------------------------------


def test_zero_shard_spec_rules():
    mesh = _lm_mesh()  # data=2, model=2, expert=2
    # first unsharded divisible dim gets 'data'
    assert R.zero_shard_spec(P(None, "model"), (64, 256), mesh) == P("data", "model")
    # dim 0 taken by 'model': falls through to dim 1
    assert R.zero_shard_spec(P("model", None), (512, 64), mesh) == P("model", "data")
    # under threshold: stays replicated
    assert R.zero_shard_spec(P(), (100,), mesh) is None
    assert R.zero_shard_spec(P(), (16384,), mesh) == P("data")
    # FSDP leaves already use 'data' — no double shard
    assert R.zero_shard_spec(P("data", "model"), (64, 256), mesh) is None
    # no divisible dim: stays replicated (prime-ish dims)
    assert R.zero_shard_spec(P(), (3, 8191), mesh, threshold=1) is None
    # trivial axis: no-op
    from ddl_tpu.parallel.sharding import LMMeshSpec, build_lm_mesh

    mesh1 = build_lm_mesh(LMMeshSpec(data=1, model=2))
    assert R.zero_shard_spec(P(None, "model"), (64, 256), mesh1) is None
    # threshold override honored
    assert R.zero_shard_spec(P(), (128,), mesh, threshold=64) == P("data")


def test_optimizer_hbm_bytes_accounting():
    mesh = _lm_mesh()
    table = R.RuleTable(
        family="t",
        rules=(("big$", P(None, "model")), ("small$", P())),
        in_specs={},
    )
    params = {"big": _leaf((64, 256)), "small": _leaf((10, 10))}
    est = R.optimizer_hbm_bytes(table, params, mesh)
    # big: 16384 elems * 8 B (mu+nu) over model=2 -> 65536 B/dev
    # small: 100 elems * 8 B replicated -> 800
    assert est["replicated_bytes"] == 64 * 256 * 8 // 2 + 100 * 8
    # zero: big additionally over data=2
    assert est["zero_bytes"] == 64 * 256 * 8 // 4 + 100 * 8
    assert est["zero_sharded_leaves"] == 1 and est["leaves"] == 2
    assert est["dp"] == 2


def test_shard_and_gather_round_trip():
    import numpy as np

    mesh = _lm_mesh()
    specs = {"w": P("data", "model"), "b": P()}
    shard, gather = R.make_shard_and_gather_fns(mesh, specs)
    tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((3,))}
    sharded = shard(tree)
    assert sharded["w"].sharding == NamedSharding(mesh, P("data", "model"))
    back = gather(sharded)
    assert isinstance(back["w"], np.ndarray)
    np.testing.assert_array_equal(back["w"], np.asarray(tree["w"]))
    np.testing.assert_array_equal(back["b"], np.asarray(tree["b"]))


def test_state_rule_shardings_cover_moments():
    """checkpoint.state_rule_shardings: moments inherit the parameter
    placement via path-embedding; step/count fall through replicated."""
    import optax

    from ddl_tpu import checkpoint as ckpt

    mesh = _lm_mesh()
    table = R.RuleTable(
        family="t", rules=(("wi/kernel$", P(None, "model")),), in_specs={},
    )
    params = {"wi": {"kernel": jnp.zeros((8, 64))}}
    tx = optax.adam(1e-3)
    state = {"step": jnp.zeros((), jnp.int32), "params": params,
             "opt_state": tx.init(params)}
    sh = ckpt.state_rule_shardings(state, table, mesh)
    assert sh["params"]["wi"]["kernel"].spec == P(None, "model")
    assert sh["opt_state"][0].mu["wi"]["kernel"].spec == P(None, "model")
    assert sh["opt_state"][0].nu["wi"]["kernel"].spec == P(None, "model")
    assert sh["step"].spec == P()
    assert sh["opt_state"][0].count.spec == P()
