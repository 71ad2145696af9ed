"""Operations and bytes the LM family's step requires, from shapes only.

Required, not executed: no recomputation is counted, a causal attention
counts the lower triangle, and a Pallas call counts what the algorithm
needs (XLA's cost analysis reads 0 for it).  A multiply-add is 2 FLOPs.
"""

from __future__ import annotations

__all__ = ["param_count", "forward_flops_per_token", "train_step_flops",
           "flash_attention_train", "tokens_per_step"]


def tokens_per_step(s: dict) -> int:
    return s["batch"] * s["seq_len"]


def param_count(s: dict) -> int:
    d, hd, f, v = s["d_model"], s["n_heads"] * s["head_dim"], s["d_ff"], s["vocab_size"]
    per_layer = 4 * d * hd + 2 * d * f + 2 * d
    return 2 * v * d + s["n_layers"] * per_layer + d


def forward_flops_per_token(s: dict) -> float:
    d, hd, f, v, t = (s["d_model"], s["n_heads"] * s["head_dim"], s["d_ff"],
                      s["vocab_size"], s["seq_len"])
    matmuls = 2 * (4 * d * hd + 2 * d * f)           # q, k, v, out, wi, wo
    attention = 2 * 2 * (t / 2) * hd                  # QK^T and PV, causal half
    return s["n_layers"] * (matmuls + attention) + 2 * d * v  # + the head


def train_step_flops(s: dict) -> float:
    """Forward + backward (2x forward) over every token of the batch."""
    return 3.0 * forward_flops_per_token(s) * tokens_per_step(s)


def flash_attention_train(s: dict) -> dict:
    """The flash kernels of one step, all layers, forward and backward:
    2 causal-half matmuls forward (QK^T, PV) and 4 backward (dV, dP, dQ,
    dK; the recomputed QK^T is not required work), each 2*T*T/2*dh per
    head; bytes are each operand read or written once in the compute
    type (q k v o forward; q k v o do read and dq dk dv written backward)
    plus the f32 row statistics."""
    b, h, dh, t, layers = s["batch"], s["n_heads"], s["head_dim"], s["seq_len"], s["n_layers"]
    one = 2.0 * t * (t / 2) * dh * b * h
    itemsize = 2 if s.get("compute_dtype", "bfloat16") in ("bfloat16", "float16") else 4
    tensor = b * h * t * dh * itemsize
    stats = b * h * t * 4
    return {
        "flops": layers * 6.0 * one,
        "bytes": layers * ((4 * tensor + stats) + (8 * tensor + 2 * stats)),
        "calls": layers * 3,
    }
