"""FLOPs accounting for bench rows: exact per-step FLOPs and MFU.

The reference publishes only wall-clock epoch times (``ipynb/main.ipynb``
cell 3) — a number that says nothing about how much of the accelerator is
used.  Here every bench row can also report

* ``tflops``: executed FLOPs per step from XLA's own cost analysis of the
  compiled program (``jit(...).lower().compile().cost_analysis()`` — the
  same machinery ``tools/split_explorer.py`` uses for stage balance), and
* ``mfu``: executed FLOP/s divided by the chip's peak dense bf16 FLOP/s.

Note on remat: cost analysis counts the FLOPs the program *executes*, so
with activation rematerialisation enabled the ratio is hardware-FLOPs
utilization (HFU) — it includes the recompute.  For rows with remat off
(the single-chip headline benches) executed == model FLOPs and the ratio
is the classic MFU.
"""

from __future__ import annotations

import jax

__all__ = [
    "device_peak_flops",
    "compiled_step_flops",
    "flash_attention_train_flops",
    "fused_dense_block_train_flops",
    "chunked_ce_extra_flops",
    "mfu",
    "append_mfu",
    "PEAK_BF16_FLOPS",
]

# jax device_kind prefix -> peak dense bf16 FLOP/s (public spec sheets)
PEAK_BF16_FLOPS = {
    "TPU v6": 918e12,  # v6e / Trillium
    "TPU v5p": 459e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v3": 123e12,
    "TPU v2": 46e12,
}


def device_peak_flops(device=None) -> float | None:
    """Peak dense bf16 FLOP/s for ``device`` (default: first device).
    None on the CPU backend — callers then omit the MFU column.  Any
    other device whose kind is not in the table is an error, not a
    default: a utilization against an assumed peak is a wrong number."""
    d = device if device is not None else jax.devices()[0]
    kind = str(d.device_kind).strip()
    # longest prefix wins so "TPU v5p" cannot fall through to a shorter key
    for k in sorted(PEAK_BF16_FLOPS, key=len, reverse=True):
        if kind.lower().startswith(k.lower()):
            return PEAK_BF16_FLOPS[k]
    if d.platform == "cpu":
        return None
    raise KeyError(
        f"no peak bf16 FLOP/s known for device kind {kind!r} (platform "
        f"{d.platform!r}); add it to PEAK_BF16_FLOPS with its source"
    )


def compiled_step_flops(fn, *args) -> float:
    """Exact executed FLOPs of one invocation of ``fn(*args)``.

    ``fn`` may be a jitted function or a plain callable (jitted here).
    A program that does not lower or compile raises."""
    lowered = (
        fn.lower(*args) if hasattr(fn, "lower") else jax.jit(fn).lower(*args)
    )
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def flash_attention_train_flops(
    batch: int,
    n_heads: int,
    seq_len: int,
    head_dim: int,
    n_layers: int,
    window: int = 0,
    remat: bool = False,
    accounting: str = "model",
) -> float:
    """Analytic attention-core FLOPs per train step for the Pallas kernel.

    XLA's cost analysis assigns ZERO FLOPs to a Pallas custom call (probed
    on v5e: an isolated `flash_attention` program reports none, and a
    flash train step's total equals the model's non-attention FLOPs
    exactly), so flash bench rows undercount MFU — increasingly with T.
    This closed form credits the visible (q, k) score pairs only, banded
    rows with banded FLOPs and not full causal ones (round-2's
    windowed-MFU caveat, resolved analytically).  It is what the
    algorithm requires, not what the kernels run: they do the band's work
    at sub-tile granularity (``ops/flash_attention.flash_tile_plan``:
    nothing outside the band, whole 256 x 256 sub-tiles on its edge), so
    at T=1024 they compute 62.5% of the square for the 50.05% credited
    here, and before PR 26 they computed all of it whenever
    ``T <= block_k``:

    * visible pairs: causal ``T(T+1)/2``; with a window W, the first W
      rows keep their triangle and the rest see W keys each —
      ``W(W+1)/2 + (T-W)W``.
    * matmuls over those pairs, 2 FLOPs/MAC each.  ``accounting`` picks
      the convention:
      - ``"model"`` (the MFU convention): the theoretical attention
        matmuls only — forward 2 (QK^T, PV) + backward 4 (dV, dP, dQ,
        dK) = 6; implementation recomputes don't count.
      - ``"executed"`` (the HFU convention): what the flash kernels
        actually run — forward 2; dQ kernel 3 (score recompute, dP, dQ);
        dK/dV kernel 4 (score recompute, dV, dP, dK) = 9, +2 when remat
        replays the forward.
      Grouped-query K/V changes none of these (the kernel computes per
      *query* head).
    """
    if accounting not in ("model", "executed"):
        raise ValueError(f"accounting must be 'model' or 'executed', got {accounting!r}")
    if window and window < seq_len:
        pairs = window * (window + 1) / 2 + (seq_len - window) * window
    else:
        pairs = seq_len * (seq_len + 1) / 2
    matmul = 2.0 * batch * n_heads * head_dim * pairs
    if accounting == "model":
        n_matmuls = 6
    else:
        n_matmuls = 11 if remat else 9
    return n_matmuls * matmul * n_layers


def fused_dense_block_train_flops(
    batch: int,
    image_size: int,
    block_config,
    growth_rate: int,
    bn_size: int,
    num_init_features: int,
    fused_blocks,
    accounting: str = "model",
) -> float:
    """Analytic train-step FLOPs of the fused dense-block Pallas kernels
    (``ops/fused_dense_block``) — XLA cost analysis assigns ZERO FLOPs
    to a Pallas custom call (same probe result as the flash kernel), so
    ``dense_block_impl="fused"`` bench rows must add the kernels' work
    back for an honest MFU.  Counts only the blocks in ``fused_blocks``
    (the others run as XLA ops and are already counted), per layer:

    * ``"model"`` (MFU convention): the theoretical matmuls at the TRUE
      input width — forward 1x1 + 3x3, backward dW/dx for each = 3 of
      each; the kernel's zero-padded full-width execution and its
      backward recompute of the forward intermediates are implementation
      overhead and do not count.
    * ``"executed"`` (HFU convention): what the kernels actually run —
      four full-padded-width 1x1 matmuls (forward, backward recompute,
      dW1, dhid) and three nine-tap 3x3 sets (forward, dh2, dW2).

    The train forward's batch-stats pass is ordinary XLA and needs no
    correction."""
    if accounting not in ("model", "executed"):
        raise ValueError(
            f"accounting must be 'model' or 'executed', got {accounting!r}"
        )
    from ddl_tpu.ops.fused_dense_block import block_pad

    bn = bn_size * growth_rate
    f = num_init_features
    hw = image_size // 4  # stem conv /2 + maxpool /2
    total = 0.0
    n_blocks = len(block_config)
    for b, n_layers in enumerate(block_config):
        if b in tuple(fused_blocks):
            s = hw * hw
            _, p_total = block_pad(f, n_layers, growth_rate)
            for i in range(n_layers):
                c_in = f + i * growth_rate
                conv1 = 2.0 * s * (
                    c_in if accounting == "model" else p_total
                ) * bn
                conv2 = 2.0 * s * 9 * bn * growth_rate
                if accounting == "model":
                    total += 3 * conv1 + 3 * conv2
                else:
                    total += 4 * conv1 + 3 * conv2
        f += n_layers * growth_rate
        if b != n_blocks - 1:
            f //= 2
            hw //= 2
    return batch * total


def chunked_ce_extra_flops(
    batch: int,
    seq_len: int,
    d_model: int,
    vocab: int,
    token_chunk: int,
    accounting: str = "model",
) -> float:
    """FLOPs correction for ``ce_chunk`` rows: XLA cost analysis counts a
    ``lax.scan`` body ONCE regardless of trip count, so a chunked head+CE
    loss (``ops/losses.fused_chunked_ce``) is undercounted by a factor of
    ``T/chunk`` on its scan bodies.  Returns the signed delta to add to
    the cost-analysis total so the loss edge is accounted at full T.

    The loss edge is three model matmuls of ``2*B*T*D*V`` each (forward
    head projection, backward dx, backward dW); the ``jax.checkpoint``
    inside the scan body replays the forward, so the *executed* count is
    four.  Cost analysis sees one fwd-scan body plus one bwd-scan body —
    four chunk-sized matmuls — hence ``counted = 4 * matmul / trips``.
    ``accounting`` follows ``flash_attention_train_flops``: "model" (MFU
    rows) targets the three theoretical matmuls — the checkpoint replay is
    implementation overhead — and "executed" (HFU rows) targets all four.
    The delta can be negative at small trip counts under "model" (counted
    replay work that the MFU convention excludes); that is the correct
    correction, not an error.
    """
    if accounting not in ("model", "executed"):
        raise ValueError(
            f"accounting must be 'model' or 'executed', got {accounting!r}"
        )
    from ddl_tpu.ops.losses import effective_chunk

    trips = seq_len // effective_chunk(token_chunk, seq_len)
    matmul = 2.0 * batch * seq_len * d_model * vocab
    target = (3.0 if accounting == "model" else 4.0) * matmul
    counted = 4.0 * matmul / trips
    return target - counted


def vocab_chunked_ce_extra_flops(
    batch: int,
    seq_len: int,
    d_model: int,
    vocab: int,
    vocab_chunk: int,
    accounting: str = "model",
) -> float:
    """FLOPs correction for ``ce_vocab_chunk`` rows (same scan-counted-once
    rule as ``chunked_ce_extra_flops``, over the VOCAB scan of
    ``ops/losses.fused_vocab_chunked_ce``).  The forward scan body holds
    one chunk-sized matmul and the hand-written backward scan body three
    (logits recompute, dx, dW): counted = 4 chunk-sized matmuls; executed
    = 4 full-V matmuls; the "model" target excludes the backward's
    recompute (3 full-V matmuls), matching the MFU convention used for
    the flash kernel and ce_chunk."""
    if accounting not in ("model", "executed"):
        raise ValueError(
            f"accounting must be 'model' or 'executed', got {accounting!r}"
        )
    from ddl_tpu.ops.losses import _vocab_blocks

    vb = _vocab_blocks(vocab, vocab_chunk)
    per_v = 2.0 * batch * seq_len * d_model
    target = (3.0 if accounting == "model" else 4.0) * per_v * vocab
    counted = 4.0 * per_v * vb
    return target - counted


def mfu(flops_per_step: float, step_time_s: float, device=None) -> float | None:
    """Fraction of peak dense bf16 FLOP/s achieved; None when peak unknown."""
    peak = device_peak_flops(device)
    if peak is None or not step_time_s > 0 or not flops_per_step > 0:
        return None
    return flops_per_step / step_time_s / peak


def append_mfu(
    out: dict, fn, step_time_s: float, *args,
    key: str = "mfu", extra_flops: float = 0.0,
) -> dict:
    """Add ``tflops_per_step`` (whenever cost analysis works) and ``key``
    (only when the chip's peak is known) to a bench result dict — the one
    reporting path shared by bench.py / bench.lm / bench.vit.  ``key`` is
    ``"mfu"`` when executed == model FLOPs (no remat) and ``"hfu"``
    otherwise (see module docstring).  ``extra_flops`` adds work cost
    analysis cannot see — Pallas custom calls report zero, so flash rows
    pass ``flash_attention_train_flops``."""
    flops = compiled_step_flops(fn, *args)
    if flops > 0:  # NaN-safe: NaN > 0 is False
        flops += extra_flops
        out["tflops_per_step"] = round(flops / 1e12, 2)
        u = mfu(flops, step_time_s)
        if u is not None:
            out[key] = round(u, 4)
    return out
