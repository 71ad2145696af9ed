"""Operations and bytes the mellum family's step requires, from shapes only.

Required, not executed: no recomputation is counted, attention counts the
keys each query really sees (the band of a sliding layer, the triangle of
a full one), an expert layer counts the rows that land on the experts
held here in expectation (``expert_top_k * experts_held / num_experts`` a
token: the softmax router loads the experts evenly at the seed's weights
and for the window's first periods; PERF.md s6 PR 33 says how far the
window's end is from it) and a Pallas call counts what the algorithm
needs.  Every layer's MLP is sparse: no dense layer, no shared expert.  A
multiply-add is 2 FLOPs.
"""

from __future__ import annotations

# what does not depend on the MLP's kind is the afmoe family's: the tokens a
# step, the keys a query sees by kind of layer, the rows routed here in
# expectation, the flash kernels' work (the same head geometry and walk)
from benchmark.work import afmoe
from benchmark.work.afmoe import (
    flash_attention_train, local_rows_per_layer, tokens_per_step, visible_keys,
)

__all__ = ["param_count", "forward_flops_per_token", "train_step_flops",
           "flash_attention_train", "expert_matmul_train", "tokens_per_step",
           "visible_keys", "local_rows_per_layer"]


def param_count(s: dict) -> int:
    d, hd, kvd, dh = (s["d_model"], s["n_heads"] * s["head_dim"],
                      s["n_kv_heads"] * s["head_dim"], s["head_dim"])
    attn = 2 * d * hd + 2 * d * kvd + 2 * dh         # q, out; k, v; q/k norms
    norms = 2 * d
    moe = d * s["num_experts"] + s["experts_held"] * 3 * d * s["moe_d_ff"]
    return 2 * s["vocab_size"] * d + d + s["n_layers"] * (attn + norms + moe)


def forward_flops_per_token(s: dict) -> float:
    d, hd, kvd = s["d_model"], s["n_heads"] * s["head_dim"], s["n_kv_heads"] * s["head_dim"]
    rows = s["expert_top_k"] * s["experts_held"] / s["num_experts"]
    total = 2.0 * d * s["vocab_size"]                              # the head
    for i in range(s["n_layers"]):
        total += 2.0 * (2 * d * hd + 2 * d * kvd)                  # q, out, k, v
        total += 2.0 * 2 * visible_keys(s, i) * hd                 # QK^T and PV
        total += 2.0 * d * s["num_experts"]                        # router
        total += 2.0 * 3 * d * s["moe_d_ff"] * rows                # the held experts
    return total


def train_step_flops(s: dict) -> float:
    """Forward + backward (2x forward) over every token of the batch."""
    return 3.0 * forward_flops_per_token(s) * tokens_per_step(s)


def expert_matmul_train(s: dict) -> dict:
    """The grouped products of one step: the afmoe family's count (3
    forward and 6 backward products over the rows routed here in
    expectation; each held expert's matrices read once a pass, their
    gradients written once in float32, the rows' operands and results
    once each) with every layer an expert layer."""
    return afmoe.expert_matmul_train(dict(s, num_dense_layers=0))
