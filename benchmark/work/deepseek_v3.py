"""Operations and bytes the deepseek_v3 family's step requires, from
shapes only.

Required, not executed: no recomputation is counted, attention counts the
keys each query really sees (the causal triangle: every layer is latent
attention over the whole past), an expert layer counts the rows that land
on the experts held here in expectation (``expert_top_k * experts_held /
num_experts`` a token) beside the shared experts' width on every token,
and a Pallas call counts what the algorithm needs.  Q and K are
``qk_nope_head_dim + qk_rope_head_dim`` wide, V ``v_head_dim``: of the
flash core's six required products, QK^T, dQ and dK run at Q's width and
PV, dP and dV at V's.  The latent's norm, the rotation and the
concatenations are elementwise and not counted, as no activation is.  A
multiply-add is 2 FLOPs.
"""

from __future__ import annotations

# the tokens a step, the rows routed here in expectation and the grouped
# products' count are the afmoe family's (the same dropless layer)
from benchmark.work import afmoe
from benchmark.work.afmoe import local_rows_per_layer, tokens_per_step

__all__ = ["param_count", "forward_flops_per_token", "train_step_flops",
           "flash_attention_train", "expert_matmul_train", "tokens_per_step",
           "visible_keys", "local_rows_per_layer", "mla_projection_params"]


def visible_keys(s: dict, i: int) -> float:
    """Keys a query of layer ``i`` sees, averaged over the positions."""
    return (s["seq_len"] + 1) / 2


def _widths(s: dict) -> tuple[int, int]:
    """(Q's and K's head width, V's)."""
    return s["qk_nope_head_dim"] + s["qk_rope_head_dim"], s["v_head_dim"]


def mla_projection_params(s: dict) -> int:
    """The four projections of one latent attention layer: q, the latent
    and the shared rotary key (kv_a), K's and V's up-projection (kv_b),
    out."""
    d, h, rank, rot = s["d_model"], s["n_heads"], s["kv_lora_rank"], s["qk_rope_head_dim"]
    dq, dv = _widths(s)
    return d * h * dq + d * (rank + rot) + rank * h * (s["qk_nope_head_dim"] + dv) + h * dv * d


def param_count(s: dict) -> int:
    d = s["d_model"]
    attn = mla_projection_params(s) + s["kv_lora_rank"]     # and the latent's norm
    dense = 3 * d * s["d_ff"]
    expert = 3 * d * s["moe_d_ff"]
    moe = (d * s["num_experts"] + s["num_experts"]          # router, selection bias
           + (s["experts_held"] + s["num_shared_experts"]) * expert)
    layers = s["n_layers"] * (attn + 2 * d) + s["num_dense_layers"] * dense
    return (2 * s["vocab_size"] * d + d                     # embedding, head; final norm
            + layers + (s["n_layers"] - s["num_dense_layers"]) * moe)


def forward_flops_per_token(s: dict) -> float:
    d, h = s["d_model"], s["n_heads"]
    dq, dv = _widths(s)
    rows = s["expert_top_k"] * s["experts_held"] / s["num_experts"]
    total = 2.0 * d * s["vocab_size"]                                 # the head
    for i in range(s["n_layers"]):
        total += 2.0 * mla_projection_params(s)                       # q, kv_a, kv_b, out
        total += 2.0 * visible_keys(s, i) * h * (dq + dv)             # QK^T and PV
        if i < s["num_dense_layers"]:
            total += 2.0 * 3 * d * s["d_ff"]
        else:
            total += 2.0 * d * s["num_experts"]                       # router
            total += 2.0 * 3 * d * s["moe_d_ff"] * (s["num_shared_experts"] + rows)
    return total


def train_step_flops(s: dict) -> float:
    """Forward + backward (2x forward) over every token of the batch."""
    return 3.0 * forward_flops_per_token(s) * tokens_per_step(s)


def flash_attention_train(s: dict) -> dict:
    """The flash kernels of one step, all layers, forward and backward: 2
    products over the visible pairs forward (QK^T at Q's width, PV at
    V's) and 4 backward (dV and dP at V's width, dQ and dK at Q's; the
    recomputed QK^T is not required work).  Bytes: each operand read or
    written once in the compute type (Q, K, dQ, dK at Q's width; V, O, dO,
    dV at V's; as many K/V heads as query heads) plus the float32 row
    statistics: forward q k v in, o and lse out; backward q k v o do, lse
    and delta in, dq dk dv out."""
    b, h, t = s["batch"], s["n_heads"], s["seq_len"]
    dq, dv = _widths(s)
    itemsize = 2 if s.get("compute_dtype", "bfloat16") in ("bfloat16", "float16") else 4
    qk, vo, stats = b * h * t * dq * itemsize, b * h * t * dv * itemsize, b * h * t * 4
    pairs = sum(t * visible_keys(s, i) for i in range(s["n_layers"]))
    fwd = 2 * qk + 2 * vo + stats
    bwd = 4 * qk + 4 * vo + 2 * stats
    return {"flops": 3 * 2.0 * (dq + dv) * pairs * b * h,
            "bytes": s["n_layers"] * (fwd + bwd), "calls": s["n_layers"] * 2}


def expert_matmul_train(s: dict) -> dict:
    """The grouped products of one step: the afmoe family's count (3
    forward and 6 backward products over the rows routed here in
    expectation; each held expert's matrices read once a pass, their
    gradients written once in float32, the rows' operands and results
    once each) over the expert layers that follow the dense ones."""
    return afmoe.expert_matmul_train(s)
