"""Dataclass configuration system with CLI overrides and named presets.

Replaces the reference's hard-coded constants (batch sizes at ``ddp.py:335`` /
``pp.py:365`` / ``ddp_n_pp.py:371``, microbatch count ``pp.py:378``, mesh shape
``ddp_n_pp.py:33``, epochs ``ddp.py:368``, dataset/checkpoint/log paths
``single.py:25,261,276``) with one typed config tree.  The four reference entry
points become four presets of the same trainer:

    single   — mesh (1,1)          (reference single.py)
    dp       — mesh (D,1)          (reference ddp.py)
    pp       — mesh (1,P)          (reference pp.py)
    dp_pp    — mesh (D,P)          (reference ddp_n_pp.py, north star (3,2))
"""

from __future__ import annotations

import argparse
import json
import os
from dataclasses import dataclass, field, fields, is_dataclass
from typing import Any, Tuple


def _env(name: str, default: str) -> str:
    return os.environ.get(name, default)


@dataclass
class MeshConfig:
    """Logical device mesh: ``(data, pipe)`` axes (reference ddp_n_pp.py:32-33)."""

    data: int = 1
    pipe: int = 1

    @property
    def num_devices(self) -> int:
        return self.data * self.pipe


@dataclass
class ModelConfig:
    """DenseNet family hyperparameters (torchvision densenet121 defaults)."""

    growth_rate: int = 32
    block_config: Tuple[int, ...] = (6, 12, 24, 16)
    num_init_features: int = 64
    bn_size: int = 4
    num_classes: int = 5
    # Stage split points: indices of dense blocks that BEGIN a new stage.
    # (2,) reproduces the reference split "features.denseblock3.denselayer1"
    # BEGINNING (pp.py:384): stage0 = stem+block1+trans1+block2+trans2,
    # stage1 = block3+trans3+block4+head.
    split_blocks: Tuple[int, ...] = (2,)
    # bfloat16 compute on TPU MXU; params stay float32.
    compute_dtype: str = "float32"
    # Rematerialise stage activations in the pipeline backward (GPipe remat).
    remat: bool = True
    # Use the Pallas normalize kernel (ops/pallas_image.py) instead of the
    # jnp path (which XLA fuses into the stem conv). Off by default; useful
    # for A/B timing on real hardware.
    pallas_normalize: bool = False
    # How dense blocks materialise their concatenative skips: "concat"
    # (textbook jnp.concatenate per layer), "packed" (TPU-native: lane-aligned
    # 128-channel feature packs, implicit concat via per-pack 1x1-conv
    # contraction, per-pack batch stats computed once — see
    # models/densenet.py PackedDenseBlock and PERF.md), or "fused"
    # (Pallas VMEM-resident whole-block kernel with custom-VJP backward
    # and two-phase train-mode BN, applied per block by
    # dense_block_fused_blocks with packed everywhere else — see
    # models/densenet.py FusedDenseBlock, ops/fused_dense_block.py and
    # PERF.md rounds 5-6).  "packed" is the default: measured +12% on
    # the bs-30 headline step (PERF.md round 4).
    dense_block_impl: str = "packed"
    # Which dense blocks (0-indexed) use the fused kernel when
    # dense_block_impl == "fused".  Default = the round-5 go/no-go list:
    # blocks 1 and 4 measured 2.9x/8.9x standalone wins; blocks 2 and 3
    # were a wash and stay packed (PERF.md round 5).
    dense_block_fused_blocks: Tuple[int, ...] = (0, 3)
    # Optional torchvision state_dict (.pth) to initialise from — the
    # ImageNet-pretrained start the reference uses (single.py:297); a
    # mismatched classifier head is skipped (the head swap, single.py:298-299).
    pretrained_path: str | None = None


@dataclass
class DataConfig:
    dataset_dir: str = field(default_factory=lambda: _env("DDL_DATASET_DIR", ""))
    # When dataset_dir is empty or missing, fall back to the synthetic
    # APTOS-shaped dataset so every config is runnable without the NAS mount.
    synthetic_num_train: int = 2930
    synthetic_num_test: int = 732
    image_size: int = 224
    num_classes: int = 5
    global_batch_size: int = 30
    eval_batch_size: int = 30
    shuffle: bool = True
    drop_last: bool = True
    num_workers: int = 2
    train_csv: str = "train.csv"
    test_csv: str = "test.csv"
    train_images: str = "train_images"
    test_images: str = "test_images"
    train_filename_col: str = "new_id_code"
    test_filename_col: str = "id_code"
    label_col: str = "diagnosis"


@dataclass
class TrainConfig:
    max_epochs: int = 30
    learning_rate: float = 1e-3  # torch.optim.Adam default (reference single.py:305)
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    # Training-schedule surface the reference lacks (train/state.py
    # build_optimizer): defaults reproduce its unconfigured Adam exactly.
    weight_decay: float = 0.0  # >0 switches to decoupled AdamW
    grad_clip_norm: float = 0.0  # >0 enables global-norm clipping
    # Compute the Adam update as ONE fusible expression per leaf
    # (train/fused_optim.fused_adam: same math and state tree as
    # optax.adam, so snapshots interoperate; the CNN step factory applies
    # it in a single pass with no separate updates tree).  Only plain
    # Adam configs fuse — weight decay / grad clipping keep the optax
    # chain.
    fused_adam: bool = True
    # ZeRO-1 optimizer-state sharding (train/fused_optim.with_zero +
    # parallel/rules.zero_shard_spec): moments and the weight update for
    # every >=8192-element leaf live on a 1/dp shard of the 'data' axis
    # (reduce-scatter grads -> per-shard fused Adam -> all-gather new
    # params), numerically identical to the replicated path.  Requires
    # the fused Adam (plain-Adam configs) and a non-pipelined strategy;
    # a no-op at mesh.data=1.
    zero_sharding: bool = False
    lr_schedule: str = "constant"  # "constant" | "cosine"
    warmup_steps: int = 0  # linear 0 -> lr ramp prepended to either schedule
    decay_steps: int = 0  # total steps for cosine (incl. warmup)
    num_microbatches: int = 5  # reference pp.py:378
    # "gpipe" (reference ScheduleGPipe semantics, pp.py:140) or "1f1b"
    # (O(stages) activation memory instead of O(microbatches))
    pipeline_schedule: str = "gpipe"
    seed: int = 42
    log_dir: str = field(default_factory=lambda: _env("DDL_LOG_DIR", "training_logs"))
    checkpoint_dir: str = field(default_factory=lambda: _env("DDL_CHECKPOINT_DIR", "checkpoints"))
    # Resume: load snapshot from <checkpoint_dir>/<job_id>/epoch_<n>
    # (reference single.py:116, ddp.py:129-133).
    snapshot_job_id: str | None = None
    snapshot_epoch: int | None = None
    # When no explicit snapshot_job_id is given, resume automatically from
    # the latest snapshot of THIS job id if one exists — the reference's
    # manual snapshot args (ddp.py:109-110) made automatic, so a
    # JobSet/SIGTERM relaunch with the same job id continues training with
    # no extra flags.
    auto_resume: bool = True
    # Save a snapshot when validation QWK improves (reference ddp.py:292-295;
    # the saves themselves are commented out in the reference — here they work).
    save_best_qwk: bool = True
    # Commit snapshots asynchronously (training continues during the write).
    async_checkpoint: bool = True
    # Snapshot GC: keep only the newest K *valid* snapshots after each
    # save (corrupt/torn ones never count toward K and are removed —
    # checkpoint.gc_snapshots).  0 = keep everything.
    keep_snapshots: int = 0
    # Failure detection (absent in the reference — SURVEY.md section 5): halt
    # with a clear diagnostic when the training loss goes non-finite.
    halt_on_nan: bool = True
    # Non-finite-loss policy: "halt" (above) or "recover" — skip the bad
    # epoch's metrics/eval/snapshot, and after nan_max_consecutive hits
    # roll back to the latest valid snapshot with a reduced-LR grace
    # window (train/recovery.RecoveryPolicy; updates scaled by
    # nan_grace_scale for nan_grace_periods epochs).
    nan_policy: str = "halt"
    nan_max_consecutive: int = 3
    nan_grace_scale: float = 0.1
    nan_grace_periods: int = 2
    # Preemption handling (absent in the reference): catch SIGTERM, finish
    # the in-flight step, checkpoint, and exit cleanly for relaunch+resume.
    preemption_save: bool = True
    log_gradient_stats: bool = False
    # Capture a jax.profiler trace of one full epoch into this directory
    # (the reference has only perf_counter timing — SURVEY.md section 5).
    profile_dir: str | None = None


@dataclass
class Config:
    strategy: str = "single"  # single | dp | pp | dp_pp
    mesh: MeshConfig = field(default_factory=MeshConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    train: TrainConfig = field(default_factory=TrainConfig)

    def validate(self) -> "Config":
        if self.strategy not in ("single", "dp", "pp", "dp_pp"):
            raise ValueError(f"unknown strategy {self.strategy!r}")
        if self.train.nan_policy not in ("halt", "recover"):
            raise ValueError(
                f"unknown nan_policy {self.train.nan_policy!r} "
                "(want 'halt' or 'recover')"
            )
        if self.train.zero_sharding and self.strategy in ("pp", "dp_pp"):
            raise ValueError(
                "zero_sharding shards the optimizer update over 'data' "
                "inside the flat DP step; the pipeline schedules apply "
                "their optimizer inside a manual shard_map region where "
                "sharding constraints cannot be planted — use strategy "
                "'single'/'dp'"
            )
        if self.train.zero_sharding and (
            not self.train.fused_adam
            or self.train.weight_decay > 0.0
            or self.train.grad_clip_norm > 0.0
        ):
            # weight decay / clipping route make_optimizer to the optax
            # chain even with fused_adam=true — catch the whole class
            # here, not deep inside with_zero (and not only at dp>1)
            raise ValueError(
                "zero_sharding requires the fused Adam path: "
                "fused_adam=true and weight_decay=0 and grad_clip_norm=0 "
                "(the sharded update is planted inside train/fused_optim's "
                "per-leaf expression; optax chains cannot be ZeRO-sharded)"
            )
        if self.strategy == "single" and self.mesh.num_devices != 1:
            raise ValueError("strategy 'single' requires a (1,1) mesh")
        if self.strategy == "dp" and self.mesh.pipe != 1:
            raise ValueError("strategy 'dp' requires pipe=1")
        if self.strategy == "pp" and self.mesh.data != 1:
            raise ValueError("strategy 'pp' requires data=1")
        if self.strategy in ("pp", "dp_pp"):
            n_stages = len(self.model.split_blocks) + 1
            if self.mesh.pipe != n_stages:
                raise ValueError(
                    f"mesh.pipe={self.mesh.pipe} must equal number of stages "
                    f"{n_stages} (split_blocks={self.model.split_blocks})"
                )
        if self.data.global_batch_size % self.mesh.data != 0:
            raise ValueError("global_batch_size must divide by mesh.data")
        local = self.data.global_batch_size // self.mesh.data
        if self.strategy in ("pp", "dp_pp") and local % self.train.num_microbatches != 0:
            raise ValueError(
                f"per-replica batch {local} must divide by "
                f"num_microbatches={self.train.num_microbatches}"
            )
        return self


# ---------------------------------------------------------------------------
# Presets mirroring the reference launch matrix (reference `command:2-34`).
# ---------------------------------------------------------------------------

def preset(name: str, **overrides: Any) -> Config:
    if name == "single":
        cfg = Config(strategy="single", mesh=MeshConfig(1, 1))
        cfg.data.global_batch_size = 30  # single.py:286
    elif name == "dp":
        cfg = Config(strategy="dp", mesh=MeshConfig(2, 1))
        # reference ddp.py:335 uses per-rank batch 15 -> global 15*D
        cfg.data.global_batch_size = 15 * cfg.mesh.data
    elif name == "pp":
        cfg = Config(strategy="pp", mesh=MeshConfig(1, 2))
        cfg.data.global_batch_size = 30  # pp.py:365
    elif name == "dp_pp":
        # north star: (3,2) mesh, per-dp-row batch 10 (ddp_n_pp.py:33,371)
        cfg = Config(strategy="dp_pp", mesh=MeshConfig(3, 2))
        cfg.data.global_batch_size = 10 * cfg.mesh.data
    else:
        raise ValueError(f"unknown preset {name!r}")
    apply_overrides(cfg, overrides)
    return cfg.validate()


# ---------------------------------------------------------------------------
# Dotted-path CLI overrides: --set train.max_epochs=3 mesh.data=4
# ---------------------------------------------------------------------------

def _coerce(current: Any, raw: str) -> Any:
    if current is None:
        try:
            return json.loads(raw)
        except json.JSONDecodeError:
            return raw
    if isinstance(current, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(current, int):
        return int(raw)
    if isinstance(current, float):
        return float(raw)
    if isinstance(current, tuple):
        return tuple(json.loads(raw))
    return raw


def apply_overrides(cfg: Config, overrides: dict[str, Any]) -> Config:
    for path, value in overrides.items():
        obj = cfg
        *parents, leaf = path.split(".")
        for p in parents:
            obj = getattr(obj, p)
        if not any(f.name == leaf for f in fields(obj)):
            raise KeyError(f"no config field {path!r}")
        current = getattr(obj, leaf)
        if isinstance(value, str) and not isinstance(current, str):
            value = _coerce(current, value)
        setattr(obj, leaf, value)
    return cfg


def to_dict(cfg: Any) -> Any:
    if is_dataclass(cfg):
        return {f.name: to_dict(getattr(cfg, f.name)) for f in fields(cfg)}
    if isinstance(cfg, tuple):
        return list(cfg)
    return cfg


def parse_cli(argv: list[str] | None = None) -> Config:
    parser = argparse.ArgumentParser(
        description="TPU-native distributed training (ddl_tpu)",
    )
    parser.add_argument(
        "--preset",
        default="single",
        choices=["single", "dp", "pp", "dp_pp"],
        help="strategy preset mirroring the reference entry points",
    )
    parser.add_argument(
        "--set",
        nargs="*",
        default=[],
        metavar="PATH=VALUE",
        help="dotted config overrides, e.g. train.max_epochs=3 mesh.data=4",
    )
    parser.add_argument("--print-config", action="store_true")
    parser.add_argument(
        "--cpu-devices",
        type=int,
        default=0,
        help="simulate N CPU devices instead of real TPUs (dev/test; same "
        "as the examples' flag)",
    )
    args = parser.parse_args(argv)
    if args.cpu_devices:
        from ddl_tpu.launch import force_cpu_devices

        force_cpu_devices(args.cpu_devices)
    overrides = {}
    for item in args.set:
        path, _, value = item.partition("=")
        overrides[path] = value
    cfg = preset(args.preset, **overrides)
    if args.print_config:
        print(json.dumps(to_dict(cfg), indent=2))
    return cfg
