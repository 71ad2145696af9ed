"""Operations and bytes the afmoe family's step requires, from shapes only.

Required, not executed: no recomputation is counted, attention counts the
keys each query really sees (the band of a sliding layer, the triangle of
a full one), an expert layer counts the rows that land on the experts
held here in expectation (``expert_top_k * experts_held / num_experts`` a
token: the router is near uniform at these weights) and a Pallas call
counts what the algorithm needs.  A multiply-add is 2 FLOPs.
"""

from __future__ import annotations

__all__ = ["param_count", "forward_flops_per_token", "train_step_flops",
           "flash_attention_train", "expert_matmul_train", "tokens_per_step",
           "visible_keys", "local_rows_per_layer"]


def tokens_per_step(s: dict) -> int:
    return s["batch"] * s["seq_len"]


def _sliding(s: dict, i: int) -> bool:
    return s["layer_types"][i] == "sliding_attention"


def _expert_layers(s: dict) -> int:
    return s["n_layers"] - s["num_dense_layers"]


def visible_keys(s: dict, i: int) -> float:
    """Keys a query of layer ``i`` sees, averaged over the positions."""
    t, w = s["seq_len"], s["sliding_window"]
    if _sliding(s, i) and t > w:
        return (w * (w + 1) / 2 + (t - w) * w) / t
    return (t + 1) / 2


def local_rows_per_layer(s: dict) -> float:
    """Token-choices a step that land on this program's experts, a layer."""
    return tokens_per_step(s) * s["expert_top_k"] * s["experts_held"] / s["num_experts"]


def param_count(s: dict) -> int:
    d, hd, kvd, dh = (s["d_model"], s["n_heads"] * s["head_dim"],
                      s["n_kv_heads"] * s["head_dim"], s["head_dim"])
    attn = 3 * d * hd + 2 * d * kvd + 2 * dh        # q, gate, out; k, v; q/k norms
    norms = 4 * d
    dense = 3 * d * s["d_ff"]
    expert = 3 * d * s["moe_d_ff"]
    moe = (d * s["num_experts"] + s["num_experts"]   # router, selection bias
           + (s["experts_held"] + s["num_shared_experts"]) * expert)
    return (2 * s["vocab_size"] * d + d
            + s["n_layers"] * (attn + norms)
            + s["num_dense_layers"] * dense + _expert_layers(s) * moe)


def forward_flops_per_token(s: dict) -> float:
    d, hd, kvd = s["d_model"], s["n_heads"] * s["head_dim"], s["n_kv_heads"] * s["head_dim"]
    total = 2.0 * d * s["vocab_size"]                             # the head
    for i in range(s["n_layers"]):
        total += 2.0 * (3 * d * hd + 2 * d * kvd)                 # q, gate, out, k, v
        total += 2.0 * 2 * visible_keys(s, i) * hd                # QK^T and PV
        if i < s["num_dense_layers"]:
            total += 2.0 * 3 * d * s["d_ff"]
        else:
            rows = s["expert_top_k"] * s["experts_held"] / s["num_experts"]
            total += 2.0 * d * s["num_experts"]                   # router
            total += 2.0 * 3 * d * s["moe_d_ff"] * (s["num_shared_experts"] + rows)
    return total


def train_step_flops(s: dict) -> float:
    """Forward + backward (2x forward) over every token of the batch."""
    return 3.0 * forward_flops_per_token(s) * tokens_per_step(s)


def flash_attention_train(s: dict) -> dict:
    """The flash kernels of one step, all layers, forward and backward: 2
    matmuls over the visible pairs forward (QK^T, PV) and 4 backward (dV,
    dP, dQ, dK; the recomputed QK^T is not required work).  Bytes: each
    operand read or written once in the compute type (q o with all the
    query heads, k v with the K/V heads) plus the f32 row statistics."""
    b, h, hkv, dh, t = s["batch"], s["n_heads"], s["n_kv_heads"], s["head_dim"], s["seq_len"]
    itemsize = 2 if s.get("compute_dtype", "bfloat16") in ("bfloat16", "float16") else 4
    qo, kv, stats = b * h * t * dh * itemsize, b * hkv * t * dh * itemsize, b * h * t * 4
    flops = sum(6 * 2.0 * dh * t * visible_keys(s, i) * b * h for i in range(s["n_layers"]))
    fwd = 2 * qo + 2 * kv + stats                    # q k v in, o and stats out
    bwd = 3 * qo + 2 * kv + 2 * stats + qo + 2 * kv  # q k v o do in; dq dk dv out
    return {"flops": flops, "bytes": s["n_layers"] * (fwd + bwd),
            "calls": s["n_layers"] * 3}


def expert_matmul_train(s: dict) -> dict:
    """The grouped products of one step, all expert layers: 3 forward
    (gate, up, down) and 6 backward (a dx and a dw each) over the rows
    routed here in expectation.  Bytes: each held expert's three matrices
    read once a pass in the compute type (forward, dx) and their
    gradients written once in float32 (dw), the rows' operands and
    results once each."""
    d, f, held = s["d_model"], s["moe_d_ff"], s["experts_held"]
    rows, layers = local_rows_per_layer(s), _expert_layers(s)
    itemsize = 2 if s.get("compute_dtype", "bfloat16") in ("bfloat16", "float16") else 4
    bank = 3 * held * d * f
    row_d, row_f = rows * d * itemsize, rows * f * itemsize
    fwd = bank * itemsize + 2 * row_d + 2 * row_f + row_f + row_d
    dx = bank * itemsize + (row_d + row_f) + 2 * (row_f + row_d)
    dw = 2 * (row_d + row_f) + (row_f + row_d) + bank * 4
    return {"flops": layers * 9 * 2.0 * rows * d * f,
            "bytes": layers * (fwd + dx + dw), "calls": layers * 9}
