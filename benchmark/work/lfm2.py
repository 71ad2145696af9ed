"""Operations and bytes the lfm2 family's step requires, from shapes only.

Required, not executed: no recomputation is counted, attention counts the
keys each query really sees (the triangle: every attention layer is a
full one), an expert layer counts the rows that land on the experts held
here in expectation (``expert_top_k * experts_held / num_experts`` a
token), a conv layer counts its two projections (the taps and the two
products are elementwise: under 0.02% of the step, not counted, as no
activation is), and a Pallas call counts what the algorithm needs.  The
head is tied to the embedding.  A multiply-add is 2 FLOPs.
"""

from __future__ import annotations

# the tokens a step, the rows routed here in expectation and the grouped
# products' count are the afmoe family's (the same dropless layer)
from benchmark.work import afmoe
from benchmark.work.afmoe import local_rows_per_layer, tokens_per_step

__all__ = ["param_count", "forward_flops_per_token", "train_step_flops",
           "expert_matmul_train", "tokens_per_step", "visible_keys",
           "local_rows_per_layer"]


def visible_keys(s: dict, i: int) -> float:
    """Keys a query of attention layer ``i`` sees, averaged over the
    positions (causal over the whole past)."""
    return (s["seq_len"] + 1) / 2


def param_count(s: dict) -> int:
    d, hd, kvd, dh = (s["d_model"], s["n_heads"] * s["head_dim"],
                      s["n_kv_heads"] * s["head_dim"], s["head_dim"])
    conv = 4 * d * d + s["short_conv"] * d              # in (3d), out; the taps
    attn = 2 * d * hd + 2 * d * kvd + 2 * dh            # q, out; k, v; q/k norms
    dense = 3 * d * s["d_ff"]
    moe = d * s["num_experts"] + s["num_experts"] + s["experts_held"] * 3 * d * s["moe_d_ff"]
    total = s["vocab_size"] * d + d                     # tied embedding, final norm
    for i, kind in enumerate(s["layer_types"]):
        total += 2 * d + (conv if kind == "conv" else attn)
        total += dense if i < s["num_dense_layers"] else moe
    return total


def forward_flops_per_token(s: dict) -> float:
    d, hd, kvd = s["d_model"], s["n_heads"] * s["head_dim"], s["n_kv_heads"] * s["head_dim"]
    rows = s["expert_top_k"] * s["experts_held"] / s["num_experts"]
    total = 2.0 * d * s["vocab_size"]                              # the tied head
    for i, kind in enumerate(s["layer_types"]):
        if kind == "conv":
            total += 2.0 * 4 * d * d                               # in (3d) and out
        else:
            total += 2.0 * (2 * d * hd + 2 * d * kvd)              # q, out, k, v
            total += 2.0 * 2 * visible_keys(s, i) * hd             # QK^T and PV
        if i < s["num_dense_layers"]:
            total += 2.0 * 3 * d * s["d_ff"]
        else:
            total += 2.0 * d * s["num_experts"]                    # router
            total += 2.0 * 3 * d * s["moe_d_ff"] * rows            # the held experts
    return total


def train_step_flops(s: dict) -> float:
    """Forward + backward (2x forward) over every token of the batch."""
    return 3.0 * forward_flops_per_token(s) * tokens_per_step(s)


def expert_matmul_train(s: dict) -> dict:
    """The grouped products of one step: the afmoe family's count (3
    forward and 6 backward products over the rows routed here in
    expectation; each held expert's matrices read once a pass, their
    gradients written once in float32, the rows' operands and results
    once each) over the expert layers that follow the dense ones."""
    return afmoe.expert_matmul_train(s)
