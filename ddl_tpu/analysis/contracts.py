"""Sharding-contract checker: abstract-eval the registered step functions.

The AST rules (``astlint.py``) see one file at a time; the bugs that cost
the most MFU live in the *composition* — a rule-table edit in
``parallel/sharding.py`` that quietly drops the ``model`` axis from the
MLP kernels replicates gigabytes per device without a single error
anywhere.  This module catches that class at trace level: each
registered step-function factory (``train/steps.py``,
``train/lm_steps.py``, ``train/vit_steps.py``, ``infer/decode.py``) is
built against a small **simulated mesh** (XLA host-platform devices — no
TPU required, the same trick the test suite uses) and validated:

* the factory's declared boundary contract (the ``.contract`` dict every
  factory attaches to its jitted train/generate function) names only
  real mesh axes, and its batch dimension is actually sharded over
  ``data`` — not silently replicated;
* the factory's **partition-rule table** (``parallel/rules.py``, carried
  in the contract as ``rule_table``) resolves every parameter leaf, its
  specs draw only on real mesh axes, and every ≥``REPLICATION_THRESHOLD``
  leaf is either sharded or replicated by an *explicit rule* — the rule
  IS the waiver, there is no hand-maintained waiver list anymore;
* the jitted program **lowers cleanly** with abstract inputs under the
  contract shardings (unknown axes, divisibility violations, and
  rule-table/spec disagreements all surface here as trace errors);
* no parameter leaf above ``REPLICATION_THRESHOLD`` elements is fully
  replicated when the mesh has a >1 axis to shard it over (unless the
  factory's contract says replication is by design — CNN DDP, serving
  replicas — or the rule table replicates it explicitly);
* with ``zero_sharding`` the optimizer moments of every eligible large
  leaf actually carry the ``data`` axis, and the probe reports the
  measured per-device optimizer-state bytes vs the replicated layout
  (the ~(dp-1)/dp reduction of PAPERS.md's cross-replica sharding);
* donation is declared by every train factory (the AST side checks the
  call sites; here the *compiled step* is probed — ``zero_donation``
  asserts the donated buffers actually alias outputs).

Probe configs are intentionally tiny (d_model 64, 2 layers) but sized so
the big kernels cross ``REPLICATION_THRESHOLD`` — a replication
regression on the probe is the same regression at 70B.
"""

from __future__ import annotations

import dataclasses
import inspect
import os
from pathlib import Path

from ddl_tpu.analysis.findings import Finding

__all__ = ["ContractReport", "REPLICATION_THRESHOLD", "run_contracts"]

# Parameter leaves at or above this many elements must not be fully
# replicated on a mesh that has a >1 non-data axis (unless the factory
# contract allows it).  Probe models are sized to push their matmul
# kernels over this line.
REPLICATION_THRESHOLD = 8192

_MIN_DEVICES = 8


def ensure_simulated_mesh(min_devices: int = _MIN_DEVICES) -> int:
    """Force the CPU host platform to expose ``min_devices`` simulated
    devices — must run before JAX initialises a backend (importing jax
    is fine; creating arrays is not).  Returns the device count actually
    available."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={min_devices}"
        ).strip()
    import jax

    try:
        # the probes never claim a chip (same reasoning as
        # tests/conftest.py); if a backend is already up this is a
        # no-op or a warning, never a crash
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass
    return len(jax.devices())


@dataclasses.dataclass
class ContractReport:
    findings: list[Finding]
    notes: list[str]


class _Probe:
    """Finding/note collector bound to one factory's source location."""

    def __init__(self, factory) -> None:
        src = inspect.getsourcefile(factory)
        root = Path(__file__).resolve().parents[2]  # repo root
        self.path = Path(src).resolve().relative_to(root).as_posix()
        self.line = inspect.getsourcelines(factory)[1]
        self.findings: list[Finding] = []
        self.notes: list[str] = []

    def add(self, rule: str, message: str) -> None:
        self.findings.append(Finding(self.path, self.line, rule, message))

    def note(self, message: str) -> None:
        self.notes.append(f"{self.path}: {message}")


def _spec_axes(spec) -> set[str]:
    axes: set[str] = set()
    for entry in spec:
        if entry is None:
            continue
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            axes.add(a)
    return axes


def _check_boundary(probe: _Probe, contract: dict, mesh) -> None:
    mesh_axes = set(mesh.axis_names)
    for name, spec in contract["in_specs"].items():
        unknown = _spec_axes(spec) - mesh_axes
        if unknown:
            probe.add(
                "contract-axis",
                f"boundary spec for {name!r} names non-mesh axes "
                f"{sorted(unknown)} (mesh has {sorted(mesh_axes)})",
            )
            continue
        first = spec[0] if len(spec) else None
        batch_axes = _spec_axes((first,))
        if "data" not in batch_axes:
            probe.add(
                "contract-boundary",
                f"batch dimension of {name!r} is not sharded over 'data' "
                f"(spec {spec}): every device would hold the full batch",
            )


def _explicit_replications(contract: dict, params) -> dict[str, str]:
    """``{leaf_path: matched_rule}`` for every leaf the factory's rule
    table replicates by explicit rule — the declarative successor of the
    retired ``replicated_ok_leaves`` waiver list."""
    table = contract.get("rule_table")
    if table is None:
        return {}
    from ddl_tpu.parallel.rules import spec_axes

    out: dict[str, str] = {}
    for name, _leaf, spec, pattern in table.provenance(params, strict=False):
        # an explicit rule whose spec names NO axis (P() or all-None —
        # the FSDP-conditional tables collapse to the latter) is
        # deliberate replication
        if pattern is not None and not spec_axes(spec):
            out[name] = pattern
    return out


def _check_params(probe: _Probe, params, mesh, contract: dict) -> None:
    import jax

    from ddl_tpu.parallel.rules import tree_path_str

    if contract["replicated_params_ok"]:
        probe.note(
            "replicated params are contractual for this factory "
            "(replication check skipped)"
        )
        return
    explicit = _explicit_replications(contract, params)
    # only non-data axes make replication a bug here: sharding params
    # over 'data' is FSDP, a deliberate opt-in, not a default expectation
    shardable = any(
        size > 1 for name, size in mesh.shape.items() if name != "data"
    )
    if not shardable:
        return
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        size = getattr(leaf, "size", 0)
        sharding = getattr(leaf, "sharding", None)
        if size < REPLICATION_THRESHOLD or sharding is None:
            continue
        if sharding.is_fully_replicated:
            name = tree_path_str(path)
            if name in explicit:
                probe.note(
                    f"replicated parameter {name} ({size} elements) is "
                    f"explicit in the rule table (rule "
                    f"{explicit[name]!r})"
                )
                continue
            probe.add(
                "contract-replicated",
                f"parameter {name} ({size} elements) is fully replicated "
                "on a shardable mesh — a silent per-device memory cost; "
                "add a rule to the family table (parallel/rules.py — "
                "an explicit P() rule if replication is intended)",
            )


def _check_rule_table(probe: _Probe, contract: dict, abs_params, mesh) -> None:
    """Validate the factory's partition-rule table directly: every leaf
    resolves, specs draw only on mesh axes, and every large leaf is
    sharded or *explicitly* replicated — the checks that used to lean on
    the hand-spec waiver list."""
    from ddl_tpu.parallel import rules as prules

    table = contract.get("rule_table")
    if table is None:
        probe.add(
            "contract-rules",
            "factory contract carries no rule_table: derive the contract "
            "from the family RuleTable (parallel/rules.py) so the probes "
            "can validate rules instead of hand-specs",
        )
        return
    mesh_axes = set(mesh.axis_names)
    for pattern, spec in table.rules:
        unknown = prules.spec_axes(spec) - mesh_axes
        if unknown:
            probe.add(
                "contract-axis",
                f"rule ({pattern!r} -> {spec}) in the {table.family!r} "
                f"table names non-mesh axes {sorted(unknown)} "
                f"(mesh has {sorted(mesh_axes)})",
            )
    try:
        prov = table.provenance(abs_params)
    except prules.UnmatchedLeafError as e:
        probe.add(
            "contract-rules",
            f"{table.family!r} rule table does not cover the family's "
            f"parameter tree: {e}",
        )
        return
    for name, leaf, spec, pattern in prov:
        size = getattr(leaf, "size", None)
        if size is None:
            import math

            shape = getattr(leaf, "shape", ())
            size = math.prod(shape) if shape else 1
        if size < REPLICATION_THRESHOLD:
            continue
        live = {
            a for a in prules.spec_axes(spec) if mesh.shape.get(a, 1) > 1
        }
        if live:
            continue
        if not prules.spec_axes(spec):
            probe.note(
                f"{table.family!r} table replicates {name} ({size} "
                f"elements) by explicit rule {pattern!r}"
            )
        else:
            probe.note(
                f"{table.family!r} table shards {name} over "
                f"{sorted(prules.spec_axes(spec))}, all trivial on this "
                "probe mesh"
            )


def _check_zero_state(probe: _Probe, state, contract: dict, mesh) -> None:
    """With ``zero_sharding`` declared: every eligible large leaf's
    moments must actually carry the 'data' axis, and the measured
    per-device optimizer bytes must show the ~(dp-1)/dp reduction."""
    import math

    import jax

    from ddl_tpu.parallel import rules as prules

    if not contract.get("zero_sharding"):
        return
    from jax.sharding import PartitionSpec as P

    table = contract.get("rule_table")
    params = state.params
    specs = (
        prules.match_partition_rules(table, params, strict=False)
        if table is not None
        else jax.tree.map(lambda _: P(), params)
    )
    spec_leaves = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, P)
    )[0]
    adam_state = state.opt_state[0]
    dp = mesh.shape.get("data", 1)
    actual = replicated = 0.0
    threshold = contract.get("zero_threshold")
    if threshold is None:  # not `or`: threshold=0 (shard everything) is valid
        threshold = prules.ZERO_THRESHOLD
    for (path, p_leaf), mu_leaf, spec in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree.leaves(adam_state.mu),
        spec_leaves,
    ):
        zspec = prules.zero_shard_spec(
            spec, tuple(p_leaf.shape), mesh, threshold=threshold
        )
        sharding = getattr(mu_leaf, "sharding", None)
        shard_elems = (
            math.prod(sharding.shard_shape(mu_leaf.shape))
            if sharding is not None else mu_leaf.size
        )
        # mu + nu, per device; vs the data-replicated layout (the leaf
        # still shards over non-data axes in both layouts)
        non_data = prules.spec_num_shards(spec, mesh) if spec else 1
        actual += 2 * shard_elems * mu_leaf.dtype.itemsize
        replicated += 2 * mu_leaf.size * mu_leaf.dtype.itemsize / non_data
        if zspec is None:
            continue
        axes = (
            prules.spec_axes(sharding.spec)
            if sharding is not None and hasattr(sharding, "spec")
            else set()
        )
        if "data" not in axes:
            probe.add(
                "contract-zero",
                f"zero_sharding is declared but the moments of "
                f"{prules.tree_path_str(path)} ({p_leaf.size} elements) "
                "are not sharded over 'data' — the leaf is eligible "
                f"(zero spec {zspec}) and silently replicated",
            )
    if replicated > 0:
        probe.note(
            f"zero_sharding: optimizer state {actual / 1024:.0f} KiB/device "
            f"vs {replicated / 1024:.0f} KiB replicated over data "
            f"(dp={dp}, reduction x{replicated / max(actual, 1):.2f})"
        )


def _donation_alias_present(compiled_text: str) -> bool:
    """True when a compiled module's text shows donated input buffers
    aliasing outputs (XLA ``input_output_alias`` / StableHLO
    ``tf.aliasing_output`` markers)."""
    return (
        "input_output_alias" in compiled_text
        or "tf.aliasing_output" in compiled_text
    )


def _lower(probe: _Probe, fn, *args, what: str) -> None:
    try:
        fn.lower(*args)
    except Exception as e:  # trace errors ARE the findings here
        msg = str(e).splitlines()[0][:200]
        probe.add(
            "contract-trace",
            f"{what} failed to lower under the probe mesh: "
            f"{type(e).__name__}: {msg}",
        )


def _tiny_lm_cfg():
    from ddl_tpu.models.transformer import LMConfig

    # d_ff * d_model = 16384 and vocab * d_model = 32768: both cross
    # REPLICATION_THRESHOLD, so a dropped sharding rule is visible
    return LMConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, head_dim=16,
        d_ff=256, compute_dtype="float32",
    )


def _cnn_build(zero: bool = False, data: int = 2, **cfg_overrides):
    """Shared tiny-CNN build: config + mesh + optimizer (ZeRO-wrapped
    when asked) + step fns + committed state.  ONE definition so every
    CNN probe — plain, fused, ZeRO, and the donation probe — compiles
    the same composition and cannot drift."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.config import ModelConfig, TrainConfig
    from ddl_tpu.models import build_stages
    from ddl_tpu.parallel.mesh import MeshSpec, build_mesh
    from ddl_tpu.train.state import create_train_state, make_optimizer
    from ddl_tpu.train.steps import make_dp_step_fns

    cfg = ModelConfig(
        growth_rate=4, block_config=(2, 2), num_init_features=8, bn_size=2,
        num_classes=5, split_blocks=(1,), compute_dtype="float32",
        remat=False, **cfg_overrides,
    )
    mesh = build_mesh(MeshSpec(data=data))
    stages = build_stages(cfg, num_stages=1)
    tx = make_optimizer(TrainConfig())  # fused Adam by default
    if zero:
        from ddl_tpu.train.fused_optim import with_zero

        # probe models are tiny; a small threshold exercises the sharded
        # expression on the same leaves a real model shards at 8192
        tx = with_zero(tx, mesh, threshold=64)
    fns = make_dp_step_fns(stages, tx, mesh, jnp.float32)
    state = create_train_state(
        stages, tx, jax.random.key(0), 16, mesh=mesh if zero else None
    )
    return fns, state, mesh


def _cnn_probe(what: str, check_fused_adam: bool = False,
               eval_too: bool = False, zero: bool = False, data: int = 2,
               **cfg_overrides) -> _Probe:
    """Shared CNN DP probe scaffolding (build via ``_cnn_build``):
    boundary/lowering/replication checks; variants differ only in model
    config overrides and extra checks."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.train.steps import make_dp_step_fns

    probe = _Probe(make_dp_step_fns)
    fns, state, mesh = _cnn_build(zero=zero, data=data, **cfg_overrides)
    _check_boundary(probe, fns.train.contract, mesh)
    if check_fused_adam and not fns.train.contract.get(
        "fused_optimizer_update"
    ):
        probe.add(
            "contract-trace",
            "fused CNN probe expected the fused Adam apply path "
            "(make_optimizer default) but the factory fell back to the "
            "two-pass optax path",
        )
    img = jax.ShapeDtypeStruct((8, 16, 16, 3), jnp.uint8)
    lbl = jax.ShapeDtypeStruct((8,), jnp.int32)
    _lower(probe, fns.train, state, img, lbl, what=f"CNN DP train step{what}")
    if eval_too:
        _lower(
            probe, fns.evaluate, state, img,
            what=f"CNN DP eval step{what}",
        )
    _check_params(probe, state.params, mesh, fns.train.contract)
    if zero:
        _check_zero_state(probe, state, fns.train.contract, mesh)
    return probe


def _probe_cnn() -> _Probe:
    return _cnn_probe("")


def _probe_cnn_zero() -> _Probe:
    """The CNN DP step with ZeRO-1 weight-update sharding on a data=4
    mesh: the reduce-scatter/fused-update/all-gather composition must
    lower, the moments must actually live data-sharded, and the probe
    reports the measured per-device optimizer-byte reduction."""
    return _cnn_probe(" (ZeRO)", zero=True, data=4)


def _probe_cnn_fused() -> _Probe:
    """The CNN DP step factory with the round-6 fused dense-block impl
    (Pallas VMEM-resident blocks + custom-VJP backward + fused Adam
    apply): the composition under test is the pallas_call pair and the
    single-pass optimizer update lowering inside the jitted SPMD step on
    a data mesh — a kernel-boundary or custom-VJP shape bug surfaces
    here before a chip bench ever runs."""
    return _cnn_probe(
        " (fused dense blocks)", check_fused_adam=True, eval_too=True,
        dense_block_impl="fused", dense_block_fused_blocks=(0, 1),
    )


def _probe_lm() -> _Probe:
    import jax
    import optax

    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns

    probe = _Probe(make_lm_step_fns)
    fns = make_lm_step_fns(
        _tiny_lm_cfg(), LMMeshSpec(data=2, model=2), optax.adam(1e-3),
        jax.random.key(0), batch=8, seq_len=32,
    )
    _check_boundary(probe, fns.train.contract, fns.mesh)
    state = fns.init_state()
    _check_rule_table(probe, fns.train.contract, state.params, fns.mesh)
    tok = jax.ShapeDtypeStruct((8, 32), jax.numpy.int32)
    _lower(probe, fns.train, state, tok, tok, what="LM train step")
    _lower(probe, fns.evaluate, state, tok, tok, what="LM eval step")
    _check_params(probe, state.params, fns.mesh, fns.train.contract)
    return probe


def _probe_lm_zero() -> _Probe:
    """The LM flat step with ZeRO-1 over a (data=4, model=2) mesh at the
    REAL 8192-element threshold (the probe model's MLP and vocab kernels
    cross it): every eligible leaf's moments must carry 'data', the step
    must lower, and the per-device optimizer bytes must show the
    ~(dp-1)/dp reduction."""
    import jax

    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.fused_optim import fused_adam
    from ddl_tpu.train.lm_steps import make_lm_step_fns

    probe = _Probe(make_lm_step_fns)
    fns = make_lm_step_fns(
        _tiny_lm_cfg(), LMMeshSpec(data=4, model=2), fused_adam(1e-3),
        jax.random.key(0), batch=8, seq_len=32, zero_sharding=True,
    )
    _check_boundary(probe, fns.train.contract, fns.mesh)
    if not fns.train.contract.get("zero_sharding"):
        probe.add(
            "contract-zero",
            "zero_sharding=True was requested but the factory contract "
            "does not declare it (with_zero wiring lost)",
        )
    state = fns.init_state()
    _check_rule_table(probe, fns.train.contract, state.params, fns.mesh)
    tok = jax.ShapeDtypeStruct((8, 32), jax.numpy.int32)
    _lower(probe, fns.train, state, tok, tok, what="LM ZeRO train step")
    _check_params(probe, state.params, fns.mesh, fns.train.contract)
    _check_zero_state(probe, state, fns.train.contract, fns.mesh)
    return probe


def _probe_zero_donation() -> _Probe:
    """Donation effectiveness across the train-step families: compile
    one step per family (CNN-ZeRO, LM, ViT) and measure how much of the
    donated train state actually aliases outputs — aliased-bytes over
    donatable-bytes from the compiled module's ``input_output_alias``
    header, parsed by the compiled-IR lint (analysis/hlolint.py).
    Donation that silently stopped aliasing would double state HBM
    right where ZeRO/donation is trying to save it."""
    import jax

    from ddl_tpu.analysis.hlolint import parse_aliases
    from ddl_tpu.obs.hbm import tree_shard_bytes
    from ddl_tpu.train.steps import make_dp_step_fns

    probe = _Probe(make_dp_step_fns)

    def check(name: str, build) -> None:
        try:
            train, state = build()
            inputs = train.probe_inputs()
            # the STEADY-STATE step: its state operand is the state the
            # step itself returned.  A fresh init_state may be laid out
            # differently from the step's output (moments replicated at
            # init, sharded after), and a leaf whose layout changes
            # cannot alias — true of step 1 only, not of the run
            first = train.lower(state, *inputs).compile()
            state = jax.tree.map(
                lambda leaf, sh: jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype, sharding=sh
                ),
                state, first.output_shardings[0],
            )
            text = train.lower(state, *inputs).compile().as_text()
        except Exception as e:
            msg = str(e).splitlines()[0][:200] if str(e) else ""
            probe.add(
                "contract-trace",
                f"{name} donation probe failed to compile: "
                f"{type(e).__name__}: {msg}",
            )
            return
        aliases = parse_aliases(text)
        if not aliases:
            probe.add(
                "contract-donation",
                f"the compiled {name} train step shows no "
                "input_output_alias: the donated state is being copied, "
                "doubling state HBM across the update",
            )
            return
        # jit flattens the state first, so its leaves are the module's
        # parameters 0..n-1; bytes are one device's shards on both sides
        # (sized from the leaves, not from ``parameter(N)`` shapes in
        # the text: nested computations number their own parameters)
        leaf_bytes = [tree_shard_bytes(leaf) for leaf in jax.tree.leaves(state)]
        aliased = sum(
            leaf_bytes[p] for _out, p, pidx in aliases
            if pidx == "" and p < len(leaf_bytes)
        )
        donatable = sum(leaf_bytes)
        probe.note(
            f"{name} donation effectiveness: {aliased}/{donatable} "
            f"bytes aliased ({aliased / max(donatable, 1):.0%})"
        )
        # partial coverage is a real memory bill, not a style point:
        # every non-aliased donated byte is double-buffered across the
        # update (the HBM ledger's optimizer row shows the hit live —
        # obs/hbm.py).  10% slack tolerates legitimately un-aliasable
        # leaves (dtype-changing casts, scalar counters).
        copied = donatable - aliased
        if donatable > 0 and copied > donatable * 0.10:
            probe.add(
                "contract-donation",
                f"{name} donation only partially aliases: "
                f"{aliased}/{donatable} donated-state bytes alias "
                f"outputs ({aliased / donatable:.0%}) — the other "
                f"{copied} bytes are copied every step and held twice "
                "across the update",
            )

    def build_cnn():
        # the same ZeRO composition cnn_dp_zero validates — one
        # builder, no drift between the two probes
        fns, state, _mesh = _cnn_build(zero=True, data=4)
        return fns.train, state

    def build_lm():
        import optax

        from ddl_tpu.parallel.sharding import LMMeshSpec
        from ddl_tpu.train.lm_steps import make_lm_step_fns

        fns = make_lm_step_fns(
            _tiny_lm_cfg(), LMMeshSpec(data=2, model=2),
            optax.adam(1e-3), jax.random.key(0), batch=8, seq_len=32,
        )
        return fns.train, fns.init_state()

    def build_vit():
        import optax

        from ddl_tpu.models.vit import ViTConfig
        from ddl_tpu.parallel.sharding import LMMeshSpec
        from ddl_tpu.train.vit_steps import make_vit_step_fns

        cfg = ViTConfig(
            image_size=16, patch_size=8, d_model=64, n_layers=2,
            n_heads=4, head_dim=16, d_ff=256, compute_dtype="float32",
            remat=False,
        )
        fns = make_vit_step_fns(
            cfg, LMMeshSpec(data=2, model=2), optax.adam(1e-3),
            jax.random.key(0), batch=8,
        )
        return fns.train, fns.init_state()

    for name, build in (
        ("CNN-ZeRO", build_cnn), ("LM", build_lm), ("ViT", build_vit),
    ):
        check(name, build)
    return probe


def _probe_vit() -> _Probe:
    import jax
    import jax.numpy as jnp
    import optax

    from ddl_tpu.models.vit import ViTConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.vit_steps import make_vit_step_fns

    probe = _Probe(make_vit_step_fns)
    cfg = ViTConfig(
        image_size=16, patch_size=8, d_model=64, n_layers=2, n_heads=4,
        head_dim=16, d_ff=256, compute_dtype="float32", remat=False,
    )
    fns = make_vit_step_fns(
        cfg, LMMeshSpec(data=2, model=2), optax.adam(1e-3),
        jax.random.key(0), batch=8,
    )
    _check_boundary(probe, fns.train.contract, fns.mesh)
    state = fns.init_state()
    # the former patch/pos-embedding waivers are explicit rules now —
    # validated against the table, not a hand list
    _check_rule_table(probe, fns.train.contract, state.params, fns.mesh)
    img = jax.ShapeDtypeStruct((8, 16, 16, 3), jnp.uint8)
    lbl = jax.ShapeDtypeStruct((8,), jnp.int32)
    _lower(probe, fns.train, state, img, lbl, what="ViT train step")
    _check_params(probe, state.params, fns.mesh, fns.train.contract)
    return probe


def _probe_decode() -> _Probe:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ddl_tpu.infer.decode import make_lm_generator
    from ddl_tpu.parallel.sharding import LMMeshSpec

    probe = _Probe(make_lm_generator)
    cfg = _tiny_lm_cfg()
    gen = make_lm_generator(
        cfg, LMMeshSpec(data=2, model=2), prompt_len=8, max_new=4, batch=2,
    )
    _check_boundary(probe, gen.contract, gen.mesh)
    from ddl_tpu.models.transformer import TransformerLM

    params = nn.meta.unbox(
        jax.eval_shape(
            lambda r: TransformerLM(cfg, None).init(
                r, jnp.zeros((2, 8), jnp.int32)
            )["params"],
            jax.random.key(0),
        )
    )
    prompt = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    _lower(
        probe, gen.jitted, params, prompt, jax.random.key(0),
        what="decode generate",
    )
    return probe


def _probe_serve_decode() -> _Probe:
    """The continuous-batching serving engine's batched decode program
    (serve/engine.py): one token for every lane over the paged KV pool.
    Validates the serving boundary (pending tokens over 'data') and that
    the gathered-block-table attention lowers under a data+model mesh —
    a rule-table edit that breaks the per-lane cache constraints
    surfaces here before a serve-bench ever runs."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ddl_tpu.models.transformer import TransformerLM
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.serve.engine import make_serve_step_fns

    probe = _Probe(make_serve_step_fns)
    cfg = _tiny_lm_cfg()
    fns = make_serve_step_fns(
        cfg, LMMeshSpec(data=2, model=2),
        block_size=8, num_blocks=16, max_batch=4,
    )
    _check_boundary(probe, fns.contract, fns.mesh)
    params = nn.meta.unbox(
        jax.eval_shape(
            lambda r: TransformerLM(cfg, None).init(
                r, jnp.zeros((2, 8), jnp.int32)
            )["params"],
            jax.random.key(0),
        )
    )
    pools = jax.eval_shape(fns.init_pools)
    # arg structs come from the engine's own probe_inputs so the probe
    # can never drift from the real call sites (shared with the
    # compiled-IR probes in analysis/hlolint.py)
    decode, _ = fns.decode_for(4, fns.max_blocks_per_seq)
    _lower(
        probe, decode, params, pools, *fns.probe_inputs("decode", 4),
        what="serve continuous-batch decode chunk",
    )
    _lower(
        probe, fns.prefill_for(8), params, pools,
        *fns.probe_inputs("prefill", 8),
        what="serve bucketed prefill",
    )
    # the round-17 chunk prefill (prefix-cache tails / long-prompt
    # chunks): masked cached attention at a traced offset over a
    # gathered pool view must lower under the same sharded mesh
    chunk, _ = fns.chunk_for(8, fns.max_blocks_per_seq, "final")
    _lower(
        probe, chunk, params, pools, *fns.probe_inputs("chunk", 8),
        what="serve chunk prefill",
    )
    return probe


def _probe_lm_pipeline() -> _Probe:
    """The pipeline-parallel LM step factory (parallel/lm_pipeline.py):
    same contract surface as the flat path (it shares
    ``finalize_step_fns``), but the program composition under test is
    the GPipe shard_map schedule over the ``pipe`` axis — a rule-table
    edit that breaks stage-stacked param placement surfaces here, not in
    the flat probe."""
    import jax
    import optax

    from ddl_tpu.parallel.lm_pipeline import make_lm_pipeline_step_fns
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns

    probe = _Probe(make_lm_pipeline_step_fns)
    # model=2 alongside pipe: embed/head run OUTSIDE the pipe region and
    # shard over 'model' — on a pipe-only mesh they replicate by design,
    # which would drown the replication check in waivers
    fns = make_lm_step_fns(
        _tiny_lm_cfg(), LMMeshSpec(data=2, pipe=2, model=2),
        optax.adam(1e-3),
        jax.random.key(0), batch=8, seq_len=32, num_microbatches=2,
    )
    _check_boundary(probe, fns.train.contract, fns.mesh)
    state = fns.init_state()
    tok = jax.ShapeDtypeStruct((8, 32), jax.numpy.int32)
    _lower(probe, fns.train, state, tok, tok, what="LM pipeline train step")
    _check_params(probe, state.params, fns.mesh, fns.train.contract)
    return probe


def _probe_lm_pipeline_zb() -> _Probe:
    """The zero-bubble (B/W-split) schedule on a (data=2, pipe=2,
    model=2) mesh: the input-cotangent-only and weight-cotangent-only
    vjps, the W ring queue carried through the scan, and the head
    epilogue cond must all lower under GSPMD auto axes beside the
    manual pipe axis — and the factory's contract must declare the
    schedule it compiled (``pipeline_schedule``, drawn from
    ``parallel/rules.PIPELINE_SCHEDULES``)."""
    import jax
    import optax

    from ddl_tpu.parallel import rules as prules
    from ddl_tpu.parallel.lm_pipeline import make_lm_pipeline_step_fns
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns

    probe = _Probe(make_lm_pipeline_step_fns)
    fns = make_lm_step_fns(
        _tiny_lm_cfg(), LMMeshSpec(data=2, pipe=2, model=2),
        optax.adam(1e-3),
        jax.random.key(0), batch=8, seq_len=32, num_microbatches=4,
        pipeline_schedule="zb",
    )
    _check_boundary(probe, fns.train.contract, fns.mesh)
    declared = fns.train.contract.get("pipeline_schedule")
    if declared != "zb":
        probe.add(
            "contract-rules",
            f"pipeline factory contract declares pipeline_schedule="
            f"{declared!r} for a zb build — the schedule facts the "
            "contract carries drifted from the compiled program",
        )
    if declared is not None and declared not in prules.PIPELINE_SCHEDULES:
        probe.add(
            "contract-rules",
            f"contract pipeline_schedule {declared!r} is not in "
            f"parallel/rules.PIPELINE_SCHEDULES {prules.PIPELINE_SCHEDULES}",
        )
    state = fns.init_state()
    tok = jax.ShapeDtypeStruct((8, 32), jax.numpy.int32)
    _lower(probe, fns.train, state, tok, tok, what="LM zb pipeline train step")
    _check_params(probe, state.params, fns.mesh, fns.train.contract)
    return probe


def _probe_vit_pipeline() -> _Probe:
    """The pipeline-parallel ViT factory (vit_steps pipeline path over
    the shared blocks-pipeline clock loop)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ddl_tpu.models.vit import ViTConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.vit_steps import make_vit_step_fns

    probe = _Probe(make_vit_step_fns)
    cfg = ViTConfig(
        image_size=16, patch_size=8, d_model=64, n_layers=2, n_heads=4,
        head_dim=16, d_ff=256, compute_dtype="float32", remat=False,
    )
    fns = make_vit_step_fns(
        cfg, LMMeshSpec(data=2, pipe=2, model=2), optax.adam(1e-3),
        jax.random.key(0), batch=8, num_microbatches=2,
    )
    _check_boundary(probe, fns.train.contract, fns.mesh)
    state = fns.init_state()
    img = jax.ShapeDtypeStruct((8, 16, 16, 3), jnp.uint8)
    lbl = jax.ShapeDtypeStruct((8,), jnp.int32)
    _lower(probe, fns.train, state, img, lbl, what="ViT pipeline train step")
    _check_params(probe, state.params, fns.mesh, fns.train.contract)
    return probe


PROBES = (
    ("cnn_dp", _probe_cnn),
    ("cnn_dp_fused", _probe_cnn_fused),
    ("cnn_dp_zero", _probe_cnn_zero),
    ("lm_flat", _probe_lm),
    ("lm_zero", _probe_lm_zero),
    ("zero_donation", _probe_zero_donation),
    ("vit_flat", _probe_vit),
    ("lm_decode", _probe_decode),
    ("serve_decode", _probe_serve_decode),
    ("lm_pipeline", _probe_lm_pipeline),
    ("lm_pipeline_zb", _probe_lm_pipeline_zb),
    ("vit_pipeline", _probe_vit_pipeline),
)


def run_contracts(min_devices: int = _MIN_DEVICES) -> ContractReport:
    """Run every registered probe; returns findings + waiver notes."""
    n = ensure_simulated_mesh(min_devices)
    findings: list[Finding] = []
    notes: list[str] = []
    if n < 4:
        notes.append(
            f"contract probes SKIPPED: only {n} device(s) visible and the "
            "probe meshes need 4 (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 before "
            "JAX initialises)"
        )
        return ContractReport(findings, notes)
    for name, probe_fn in PROBES:
        try:
            probe = probe_fn()
        except Exception as e:  # a probe that cannot even build IS a finding
            msg = str(e).splitlines()[0][:200] if str(e) else ""
            findings.append(
                Finding(
                    "ddl_tpu/analysis/contracts.py", 1, "contract-trace",
                    f"probe {name!r} failed to build its step functions: "
                    f"{type(e).__name__}: {msg}",
                )
            )
            continue
        findings.extend(probe.findings)
        notes.extend(probe.notes)
    return ContractReport(sorted(findings), notes)
