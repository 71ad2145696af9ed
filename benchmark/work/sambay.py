"""Operations and bytes the SambaY family's step requires, from shapes only.

Required, not executed: no recomputation is counted, attention counts the
keys each query really sees (the band of a sliding layer, the triangle of
a full or a cross layer) and each score once (a pair's ``A1`` and ``A2``
against ``V = [v1, v2]`` of twice the head's width, however many kernel
calls the program makes of it), the scan counts its recurrence and each
operand once, chunk-boundary states and recomputed chunks not at all.  A
multiply-add is 2 FLOPs.
"""

from __future__ import annotations

__all__ = ["param_count", "forward_flops_per_token", "train_step_flops",
           "selective_scan_train", "flash_diff_train", "tokens_per_step",
           "visible_keys", "layers_of"]

_ATTENTION = ("sliding_attention", "full_attention", "cross_attention")
# multiplies and adds of one step of the recurrence for one channel's one
# state: dt * A, the decay times h, (dt u) * B, their sum, h * C and its
# sum over the states (the exponential is not counted)
_SCAN_FLOPS = 7.0


def tokens_per_step(s: dict) -> int:
    return s["batch"] * s["seq_len"]


def layers_of(s: dict, *kinds: str) -> list[int]:
    return [i for i, k in enumerate(s["layer_types"]) if k in kinds]


def _ssm(s: dict):
    d = s["d_model"]
    return d, s["ssm_expand"] * d, s["ssm_state"], s["ssm_conv"], s["ssm_dt_rank"]


def visible_keys(s: dict, i: int) -> float:
    """Keys a query of layer ``i`` sees, averaged over the positions."""
    t, w = s["seq_len"], s["sliding_window"]
    if s["layer_types"][i] == "sliding_attention" and t > w:
        return (w * (w + 1) / 2 + (t - w) * w) / t
    return (t + 1) / 2


def _mixer_matrix_params(s: dict, kind: str) -> int:
    """Parameters of a mixer that a token multiplies (matrices only)."""
    d, d_in, n, _, r = _ssm(s)
    hd, kvd = s["n_heads"] * s["head_dim"], s["n_kv_heads"] * s["head_dim"]
    return {
        "mamba": 2 * d * d_in + d_in * (r + 2 * n) + r * d_in + d_in * d,
        "gmu": 2 * d * d_in,
        "cross_attention": 2 * d * hd,
        "sliding_attention": 2 * d * hd + 2 * d * kvd,
        "full_attention": 2 * d * hd + 2 * d * kvd,
    }[kind]


def param_count(s: dict) -> int:
    d, d_in, n, kc, _ = _ssm(s)
    hd, kvd, dh = s["n_heads"] * s["head_dim"], s["n_kv_heads"] * s["head_dim"], s["head_dim"]
    total = s["vocab_size"] * d + 2 * d              # tied embedding, final norm
    for kind in s["layer_types"]:
        total += 4 * d + 3 * d * s["d_ff"]           # two LayerNorms, the MLP
        total += _mixer_matrix_params(s, kind)
        if kind == "mamba":                          # conv and its bias, dt's bias, A, D
            total += kc * d_in + d_in + d_in + d_in * n + d_in
        elif kind in _ATTENTION:                     # biases, lambdas, the pair norm
            total += hd + d + 4 * dh + 2 * dh
            total += 2 * kvd if kind != "cross_attention" else 0
    return total


def forward_flops_per_token(s: dict) -> float:
    d, d_in, n, kc, _ = _ssm(s)
    hd = s["n_heads"] * s["head_dim"]
    total = 2.0 * d * s["vocab_size"]                             # the tied head
    for i, kind in enumerate(s["layer_types"]):
        total += 2.0 * (3 * d * s["d_ff"] + _mixer_matrix_params(s, kind))
        if kind == "mamba":
            total += 2.0 * kc * d_in + _SCAN_FLOPS * d_in * n
        elif kind in _ATTENTION:
            # a pair: two score products over head_dim, two value products
            # over 2 * head_dim = 6 * head_dim multiply-adds a visible key
            total += 2.0 * 3 * visible_keys(s, i) * hd
    return total


def train_step_flops(s: dict) -> float:
    """Forward + backward (2x forward) over every token of the batch."""
    return 3.0 * forward_flops_per_token(s) * tokens_per_step(s)


def selective_scan_train(s: dict) -> dict:
    """The scan kernels of one step, all Mamba layers, forward and
    backward.  FLOPs: the recurrence forward and twice that back.  Bytes,
    float32: forward reads u, dt (d_in a token each), B, C (N each), A and
    D and writes y; backward reads those and dy and writes du, ddt, dB,
    dC, dA, dD.  The bound is the bytes' (about 160 KB a token against
    0.6 MFLOP); the kernels are elementwise work on the vector unit and
    sit far under it."""
    _, d_in, n, _, _ = _ssm(s)
    layers, tokens = len(layers_of(s, "mamba")), tokens_per_step(s)
    row, col, fixed = 4 * d_in * tokens, 4 * n * tokens, 4 * (d_in * n + d_in)
    fwd = 2 * row + 2 * col + fixed + row
    bwd = 3 * row + 2 * col + fixed + 2 * row + 2 * col + fixed
    return {"flops": layers * 3 * _SCAN_FLOPS * d_in * n * tokens,
            "bytes": layers * (fwd + bwd), "calls": layers * 2}


def flash_diff_train(s: dict) -> dict:
    """The attention kernels of one step, every self and cross layer,
    forward and backward, for differential attention: a pair of heads
    needs its two score matrices once and each against V of twice the
    head's width, forward 6 * head_dim multiply-adds a visible key and
    pair, backward twice that (dV, dP, dQ, dK; the recomputed scores are
    not required work).  Bytes: q, o, do, dq with all the query heads and
    k, v, dk, dv with the K/V heads once each in the compute type, plus
    the float32 row statistics of each query head."""
    b, h, hkv, dh, t = s["batch"], s["n_heads"], s["n_kv_heads"], s["head_dim"], s["seq_len"]
    itemsize = 2 if s.get("compute_dtype", "bfloat16") in ("bfloat16", "float16") else 4
    layers = layers_of(s, *_ATTENTION)
    qo, kv, stats = b * h * t * dh * itemsize, b * hkv * t * dh * itemsize, b * h * t * 4
    flops = sum(3 * 2.0 * 3 * dh * t * visible_keys(s, i) * b * h for i in layers)
    fwd = 2 * qo + 2 * kv + stats
    bwd = 3 * qo + 2 * kv + 2 * stats + qo + 2 * kv
    return {"flops": flops, "bytes": len(layers) * (fwd + bwd), "calls": len(layers) * 2}
