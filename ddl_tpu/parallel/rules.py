"""Declarative partition rules: regex-over-param-path -> PartitionSpec.

Before this module, parameter placement lived in two places that could
drift: the flax logical-axis annotations inside the models (resolved
through ``lm_logical_rules``) and the hand-written ``PartitionSpec``
literals + ``.contract`` dicts in every step factory.  Onboarding a new
model family meant re-deriving both, and the optimizer-state sharding
work (ZeRO) had nowhere to hang: the moments' placement was whatever
``tx.init`` propagation produced.

This module makes partitioning a *table*, in the ``match_partition_rules``
style of the public LLM-training frameworks (SNIPPETS.md [1]/[3]): an
ordered list of ``(regex, PartitionSpec)`` rules matched against each
parameter's ``/``-joined tree path, **first match wins**, scalars and
single-element leaves replicate, and a leaf no rule matches is a loud
``UnmatchedLeafError`` — a new parameter cannot be silently replicated
by omission.  Per-family tables (CNN / LM / ViT / decode) carry the
family's jit-boundary batch specs and derive the machine-readable
``.contract`` the step factories attach, so the sharding-contract
checker (``analysis/contracts.py``) validates the *table* instead of a
hand-maintained waiver list.  Because ``re.search`` matches anywhere in
the path, the same table resolves optimizer moments: a ``mu/nu`` leaf's
path embeds the parameter path (``0/mu/block0/attn/q/kernel``), so
Adam state inherits parameter placement for free (``strict=False`` lets
non-parameter leaves — counts, the step — fall through to replicated).

The LM/ViT tables reproduce the models' logical-axis resolution exactly
(asserted leaf-by-leaf by ``tests/test_partition_rules.py``); the
*activations* keep their ``nn.with_logical_constraint`` annotations —
this table owns parameter (and derived optimizer-state) placement.

``zero_shard_spec`` is the ZeRO-1 derivation on top of a resolved rule
table: given a parameter's spec and shape, pick the first unsharded
dimension divisible by the ``data``-axis size and shard the *optimizer
state and weight update* over it (the cross-replica weight-update
sharding of PAPERS.md's "Automatic Cross-Replica Sharding" paper —
``train/fused_optim.py`` consumes it).

Because placement is a pure function of the parameter path — never of
the mesh extent — the tables are what make elastic pod scale-down a
*derivable* respec: a relaunch on N-1 hosts re-enters the same table
with a smaller ``data`` axis (``DDL_NUM_PROCESSES`` from the agreed
membership, see ``supervisor.py``) and every parameter lands in the
same logical position; only the data-parallel extent shrinks.  The
same property carries the GROW direction (elastic scale-up, round 24):
a relaunch into a larger world re-enters the table with the bigger
``data`` axis, ``zero_shard_spec`` re-picks the same dimension (the
divisibility test only loosens as the axis grows back toward the size
the model was originally validated for), and the restore re-shards the
moments into the new layout with no extra mechanism
(``checkpoint.state_rule_shardings`` + the global-array restore —
tests/test_zero_sharding.py pins dp=2 -> dp=4 -> dp=2 bit-identity).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "UnmatchedLeafError",
    "RuleTable",
    "match_partition_rules",
    "match_with_provenance",
    "make_shard_and_gather_fns",
    "tree_path_str",
    "cnn_rules",
    "lm_rules",
    "vit_rules",
    "decode_rules",
    "zero_shard_spec",
    "zero_gather_plan",
    "spec_axes",
    "spec_num_shards",
    "optimizer_hbm_bytes",
    "ZERO_THRESHOLD",
    "PIPELINE_SCHEDULES",
    "BATCH_SPEC",
    "IMAGE_SPEC",
    "TOKEN_SPEC",
    "DECODE_TOKEN_SPEC",
    "LM_MANUAL_ATTN_SPEC",
]

# Parameter leaves at or above this many elements get their optimizer
# state ZeRO-sharded over 'data' (below it the all-gather latency costs
# more than the replicated bytes); the same line the contract checker
# draws for silent replication (analysis/contracts.REPLICATION_THRESHOLD).
ZERO_THRESHOLD = 8192

# The blocks-pipeline schedule vocabulary (parallel/lm_pipeline.py):
# "gpipe" (autodiff through the forward scan; virtual_stages > 1 makes
# it the interleaved schedule), "1f1b" (hand-written interleaved
# forward/backward), "zb" (zero-bubble: 1F1B with the backward split
# into B/W and W deferred into the cooldown ticks).  The step
# factories validate against this tuple and stamp the selected
# schedule into their boundary contract (``pipeline_schedule``), which
# the contract probes (analysis/contracts.py) check membership of —
# one vocabulary, declared where the rest of the partitioning facts
# live.
PIPELINE_SCHEDULES = ("gpipe", "1f1b", "zb")

# ---------------------------------------------------------------------------
# Named jit-boundary batch specs.  Defined HERE (not in the step
# factories) so factories, contracts, and tests agree by construction —
# the step-factory modules themselves are lint-banned from hand-writing
# PartitionSpec axis literals (astlint 'pspec-hand-rolled').
# ---------------------------------------------------------------------------

# CNN image/label batches on the (data, pipe) mesh.
BATCH_SPEC = P("data")
# ViT image/label batches (the family does not use the expert axis).
IMAGE_SPEC = P("data")
# LM token batches: batch over data x expert (outside MoE layers the
# expert axis is extra data parallelism), sequence over seq.
TOKEN_SPEC = P(("data", "expert"), "seq")
# Decode prompt/output batches: batch over data; heads shard over
# 'model' inside the program.
DECODE_TOKEN_SPEC = P("data")
# Boundary of the manual attention cores (ring / Ulysses / flash
# shard_map): batch over data x expert, sequence over seq, heads over
# model, head_dim local.
LM_MANUAL_ATTN_SPEC = P(("data", "expert"), "seq", "model", None)


class UnmatchedLeafError(ValueError):
    """A non-scalar leaf matched no partition rule.  Carries the paths so
    the fix (add a rule) is obvious from the message."""

    def __init__(self, family: str, paths: list[str]) -> None:
        self.family = family
        self.paths = list(paths)
        listed = ", ".join(self.paths[:8])
        more = f" (+{len(self.paths) - 8} more)" if len(self.paths) > 8 else ""
        super().__init__(
            f"no partition rule in the {family!r} table matches leaf path(s) "
            f"{listed}{more}; every parameter must be placed explicitly "
            "(add a rule to parallel/rules.py — P() for deliberate "
            "replication)"
        )


def tree_path_str(key_path) -> str:
    """``/``-joined tree path (DictKey / GetAttrKey / SequenceKey all
    stringify differently; normalise like ``checkpoint._kp_norm``)."""
    return "/".join(
        str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))
        for k in key_path
    )


def _is_spec(x) -> bool:
    return isinstance(x, P)


def _leaf_size(leaf) -> int:
    shape = getattr(leaf, "shape", None)
    if shape is None:
        return 1
    return math.prod(shape) if shape else 1


def _match_leaves(rules, tree, family: str, strict: bool):
    """Yield ``(path, leaf, spec, pattern)`` per leaf; ``pattern`` is the
    matched rule's regex (None for the scalar/fallthrough default)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out, unmatched = [], []
    for kp, leaf in flat:
        name = tree_path_str(kp)
        if _leaf_size(leaf) <= 1:
            out.append((name, leaf, P(), None))
            continue
        for pattern, spec in rules:
            if re.search(pattern, name) is not None:
                out.append((name, leaf, spec, pattern))
                break
        else:
            unmatched.append(name)
            out.append((name, leaf, P(), None))
    if strict and unmatched:
        raise UnmatchedLeafError(family, unmatched)
    return out, treedef


def match_partition_rules(rules, tree, *, strict: bool = True):
    """PartitionSpec pytree for ``tree`` under first-match-wins ``rules``
    (``[(regex, PartitionSpec), ...]`` or a ``RuleTable``).  Scalar and
    single-element leaves replicate without consulting the table; with
    ``strict`` (the default) an unmatched non-scalar leaf raises
    ``UnmatchedLeafError``, with ``strict=False`` it replicates — the
    mode for whole *state* trees, whose non-parameter leaves (step,
    Adam's count) have no rules but whose moment leaves embed the
    parameter path and match normally."""
    family = getattr(rules, "family", "<anonymous>")
    rules = getattr(rules, "rules", rules)
    leaves, treedef = _match_leaves(rules, tree, family, strict)
    return treedef.unflatten([spec for _, _, spec, _ in leaves])


def match_with_provenance(rules, tree, *, strict: bool = True):
    """Like ``match_partition_rules`` but returns a flat list of
    ``(path, leaf, spec, matched_pattern)`` — the contract probes use the
    pattern to distinguish *explicit* replication (a rule that maps to
    ``P()``) from a replication bug."""
    family = getattr(rules, "family", "<anonymous>")
    rules = getattr(rules, "rules", rules)
    leaves, _ = _match_leaves(rules, tree, family, strict)
    return leaves


def make_shard_and_gather_fns(mesh: Mesh, specs):
    """``(shard, gather)`` tree functions from a resolved spec pytree.

    ``shard(tree)`` device_puts every leaf onto ``mesh`` under its spec —
    how a checkpoint restored as host/replicated arrays enters rule
    placement; ``gather(tree)`` fetches every leaf fully to host (numpy)
    — the inverse, for writing topology-independent snapshots or
    comparing sharded and replicated states leaf-by-leaf."""
    import numpy as np

    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=_is_spec
    )

    def shard(tree):
        return jax.tree.map(jax.device_put, tree, shardings)

    def gather(tree):
        return jax.tree.map(
            lambda x: np.asarray(jax.device_get(x)), tree
        )

    return shard, gather


@dataclasses.dataclass(frozen=True)
class RuleTable:
    """One model family's partitioning, as data.

    ``rules`` place parameters (and, via path-embedding, optimizer
    moments); ``in_specs`` are the family's jit-boundary batch specs;
    ``replicated_params_ok``/``donate_state`` feed the derived contract.
    """

    family: str
    rules: tuple[tuple[str, P], ...]
    in_specs: dict[str, P]
    replicated_params_ok: bool = False
    donate_state: bool = True

    def specs(self, tree, *, strict: bool = True):
        return match_partition_rules(self, tree, strict=strict)

    def shardings(self, tree, mesh: Mesh, *, strict: bool = True):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            self.specs(tree, strict=strict),
            is_leaf=_is_spec,
        )

    def provenance(self, tree, *, strict: bool = True):
        return match_with_provenance(self, tree, strict=strict)

    def contract(self, **extra) -> dict:
        """The machine-readable ``.contract`` dict the step factories
        attach to their jitted train/generate functions — derived from
        the table instead of hand-written, and carrying the table itself
        so ``analysis/contracts.py`` validates rules, not waivers."""
        c = {
            "in_specs": dict(self.in_specs),
            "donate_state": self.donate_state,
            "replicated_params_ok": self.replicated_params_ok,
            "rule_table": self,
        }
        c.update(extra)
        return c


# ---------------------------------------------------------------------------
# family tables
# ---------------------------------------------------------------------------


def _transformer_block_rules(E) -> tuple[tuple[str, P], ...]:
    """The decoder/encoder block shared by the LM and ViT families:
    attention QKV column-parallel and the out projection row-parallel
    over 'model' (Megatron split), MLP the same, MoE experts over
    'expert'; ``E`` is the embed-dimension axis — 'data' under FSDP
    (ZeRO-3-style parameter sharding), unsharded otherwise.  The gated
    block's leaves (``LMConfig.attn_gate``, ``mlp_gated``, the dropless
    router's shared expert and selection bias) split as their twins do:
    the attention gate with q, a gate matrix with its up matrix, a shared
    expert as a dense MLP; the bias is one small vector, replicated; the
    q/k norms' scales fall under the norm rule."""
    return (
        (r"attn/(q|k|v|gate)/kernel$", P(E, "model")),
        (r"attn/out/kernel$", P("model", E)),
        (r"(mlp|moe/shared)/(wi|wg)/kernel$", P(E, "model")),
        (r"(mlp|moe/shared)/wo/kernel$", P("model", E)),
        (r"moe/router/kernel$", P(E, "expert")),
        (r"moe/bias$", P()),
        (r"moe/(wi|wg)$", P("expert", E, "model")),
        (r"moe/wo$", P("expert", "model", E)),
        (r"norm\w*/(scale|bias)$", P()),
        # a hybrid stack's mixers (models/transformer.py: DiffAttention,
        # Mamba, Gmu).  Differential and cross attention split by heads as
        # attention does (a cross layer's ``xattn/q/kernel`` and
        # ``xattn/out/kernel`` are found by the attention rules above: the
        # patterns search), biases with their kernels' output axis; the
        # lambda vectors and the pair norm's scale are head_dim-sized,
        # replicated.  The Mamba layer and the GMU split d_inner over
        # 'model' as an MLP splits its hidden width: the scan's channels
        # are independent, the in projections column-parallel, x_proj and
        # the out projections row-parallel.
        (r"x?attn/(q|k|v)/bias$", P("model")),
        (r"x?attn/out/bias$", P()),
        (r"x?attn/lambda_(q|k)[12]$", P()),
        (r"x?attn/subln/scale$", P()),
        (r"ssm/in_(x|z)/kernel$", P(E, "model")),
        (r"ssm/conv/kernel$", P(None, "model")),
        (r"ssm/(conv|dt_proj)/bias$", P("model")),
        (r"ssm/x_proj/kernel$", P("model", None)),
        (r"ssm/dt_proj/kernel$", P(None, "model")),
        (r"ssm/A_log$", P("model", None)),
        (r"ssm/D$", P("model")),
        (r"(ssm|gmu)/out_proj/kernel$", P("model", E)),
        (r"gmu/in_proj/kernel$", P(E, "model")),
    )


def lm_rules(fsdp: bool = False) -> RuleTable:
    """The transformer LM family (``models/transformer.py``): TP over
    'model' (vocab/heads/MLP-hidden), experts over 'expert', embed dim
    over 'data' with ``fsdp`` — leaf-for-leaf the resolution the model's
    logical-axis annotations produce."""
    E = "data" if fsdp else None
    return RuleTable(
        family="lm",
        rules=_transformer_block_rules(E) + (
            (r"embed/embedding$", P("model", E)),
            (r"lm_head/kernel$", P("model", E)),
        ),
        in_specs={"inputs": TOKEN_SPEC, "targets": TOKEN_SPEC},
    )


def vit_rules(fsdp: bool = False) -> RuleTable:
    """The ViT family (``models/vit.py``).  The patch/position embeddings
    and the tiny classifier head replicate by *explicit rule* (formerly
    contract waivers): their embed dimension is the only shardable one,
    deliberately left whole without FSDP — the probes report these as
    explicit replication, not silent."""
    E = "data" if fsdp else None
    return RuleTable(
        family="vit",
        rules=_transformer_block_rules(E) + (
            (r"patch_embed/kernel$", P(None, None, None, E)),
            (r"patch_embed/bias$", P()),
            (r"pos_embed$", P(None, None, E)),
            (r"head/kernel$", P(E, None)),
            (r"head/bias$", P()),
        ),
        in_specs={"images": IMAGE_SPEC, "labels": IMAGE_SPEC},
    )


def cnn_rules() -> RuleTable:
    """The DenseNet family: DDP keeps full parameter replicas by design
    (gradients all-reduce over 'data'; there is no tensor-parallel axis
    in this family), so one explicit catch-all replication rule places
    everything — and the derived contract says replication is
    contractual, which is the probe waiver."""
    return RuleTable(
        family="cnn",
        rules=((r".", P()),),
        in_specs={"images": BATCH_SPEC, "labels": BATCH_SPEC},
        replicated_params_ok=True,
    )


def decode_rules() -> RuleTable:
    """The LM decode/serving surface: the same parameter placement as LM
    training (a training snapshot decodes as-is), no state donation, and
    replication allowed by contract — serving replicas on a
    model-axis-free mesh intentionally hold full copies."""
    base = lm_rules(fsdp=False)
    return RuleTable(
        family="decode",
        rules=base.rules,
        in_specs={"prompt": DECODE_TOKEN_SPEC},
        replicated_params_ok=True,
        donate_state=False,
    )


# ---------------------------------------------------------------------------
# ZeRO derivation + optimizer-state HBM accounting
# ---------------------------------------------------------------------------


def _norm_entries(spec, ndim: int) -> tuple:
    entries = tuple(spec) if spec is not None else ()
    return entries + (None,) * (ndim - len(entries))


def spec_axes(spec) -> set[str]:
    """Mesh-axis names a PartitionSpec draws on (tuples flattened)."""
    axes: set[str] = set()
    for e in tuple(spec or ()):
        if e is None:
            continue
        for a in (e if isinstance(e, tuple) else (e,)):
            axes.add(a)
    return axes


_spec_axes = spec_axes


def spec_num_shards(spec, mesh: Mesh) -> int:
    """Devices one leaf is split across under ``spec`` (its per-device
    byte divisor)."""
    n = 1
    for a in _spec_axes(spec):
        n *= mesh.shape.get(a, 1)
    return n


def zero_shard_spec(
    spec,
    shape,
    mesh: Mesh,
    axis: str = "data",
    threshold: int = ZERO_THRESHOLD,
):
    """The ZeRO-1 spec for one parameter leaf, or None when the leaf
    stays replicated over ``axis``.

    Adds ``axis`` to the first unsharded dimension whose size divides by
    the axis size — the shard the optimizer moments live at and the
    weight update computes at (reduce-scattered gradients in,
    all-gathered parameters out).  None when: the leaf is under
    ``threshold`` elements (gather latency would cost more than the
    replicated bytes), the axis is trivial, the spec already uses it
    (FSDP — the state is already sharded over data), or no dimension
    divides."""
    size = math.prod(shape) if shape else 1
    if size < threshold:
        return None
    dp = mesh.shape.get(axis, 1)
    if dp <= 1:
        return None
    entries = _norm_entries(spec, len(shape))
    if axis in _spec_axes(entries):
        return None
    for i, (e, dim) in enumerate(zip(entries, shape)):
        if e is None and dim % dp == 0:
            return P(*entries[:i], axis, *entries[i + 1:])
    return None


def zero_gather_plan(
    table: RuleTable,
    abstract_params,
    mesh: Mesh,
    axis: str = "data",
    threshold: int | None = None,
) -> dict:
    """The expected all-gather geometry of a ZeRO-1 program, derived
    from the rule table — the leaf-size/spec provenance the compiled-IR
    lint (``analysis/hlolint.py``) checks GSPMD's emitted gathers
    against.

    Per eligible leaf (``zero_shard_spec`` accepts it): its *gather
    shape* — the full shape divided by the leaf's non-``axis`` shard
    counts — which is what the weight-update all-gather must produce
    (shard-sized operand in, non-data-shard out).  ``leaf_shard_shapes``
    additionally lists every ≥threshold leaf's shard shape, eligible or
    not: backward-pass gathers (embedding scatter-add) legitimately
    produce param-shaped outputs, so they are allowed, while a gather
    producing any *other* large shape has no business in the step."""
    if threshold is None:
        threshold = ZERO_THRESHOLD
    eligible: list[dict] = []
    leaf_shard_shapes: set[tuple[int, ...]] = set()
    for name, leaf, spec, _pat in table.provenance(
        abstract_params, strict=False
    ):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        size = math.prod(shape) if shape else 1
        if size < threshold:
            continue
        entries = _norm_entries(spec, len(shape))
        shard = tuple(
            dim // math.prod(
                mesh.shape.get(a, 1)
                for a in ((e,) if not isinstance(e, tuple) else e)
                if a != axis
            ) if e is not None else dim
            for e, dim in zip(entries, shape)
        )
        leaf_shard_shapes.add(shard)
        zspec = zero_shard_spec(spec, shape, mesh, axis, threshold)
        if zspec is None:
            continue
        eligible.append({
            "name": name,
            "size": size,
            "shape": list(shape),
            "gather_shape": list(shard),
        })
    return {
        "axis": axis,
        "threshold": threshold,
        "eligible": eligible,
        "gather_shapes": sorted(
            {tuple(leaf["gather_shape"]) for leaf in eligible}
        ),
        "leaf_shard_shapes": sorted(leaf_shard_shapes),
    }


def optimizer_hbm_bytes(
    table: RuleTable,
    abstract_params,
    mesh: Mesh,
    axis: str = "data",
    threshold: int = ZERO_THRESHOLD,
    moment_bytes_per_param: int = 8,
) -> dict:
    """Per-device Adam-state HBM estimate from the rule table: mu + nu
    per parameter leaf (f32, ``moment_bytes_per_param`` = 2 x 4 bytes),
    divided by each leaf's shard count — replicated-over-data vs
    ZeRO-sharded.  Pure accounting (eval_shape trees in, bytes out); the
    ``ddl_tpu bench`` HBM column and the ``opt_hbm_bytes`` obs gauge
    read it."""
    replicated = zero = 0.0
    leaves = sharded = 0
    for _name, leaf, spec, _pat in table.provenance(abstract_params):
        shape = getattr(leaf, "shape", ())
        size = math.prod(shape) if shape else 1
        bytes_ = size * moment_bytes_per_param
        leaves += 1
        replicated += bytes_ / spec_num_shards(spec, mesh)
        zspec = zero_shard_spec(spec, shape, mesh, axis, threshold)
        if zspec is not None:
            sharded += 1
            zero += bytes_ / spec_num_shards(zspec, mesh)
        else:
            zero += bytes_ / spec_num_shards(spec, mesh)
    return {
        "replicated_bytes": int(replicated),
        "zero_bytes": int(zero),
        "dp": mesh.shape.get(axis, 1),
        "leaves": leaves,
        "zero_sharded_leaves": sharded,
    }
