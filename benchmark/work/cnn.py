"""Operations the DenseNet family's step requires, from shapes only:
convolutions and the classifier, a multiply-add as 2 FLOPs, forward plus
backward (2x forward).  BatchNorm, ReLU, pooling and Adam are bandwidth,
not FLOPs, and are left out: the share of the peak this gives is the
matrix unit's."""

from __future__ import annotations

__all__ = ["forward_flops_per_image", "train_step_flops", "param_count"]


def _convs(s: dict):
    """[(out_h, out_w, k, c_in, c_out)] of every convolution."""
    size = s.get("image_size", 224)
    k, bn = s["growth_rate"], s["bn_size"]
    c = s["num_init_features"]
    hw = size // 2
    out = [(hw, hw, 7, 3, c)]
    hw //= 2  # max-pool
    blocks = s["block_config"]
    for b, layers in enumerate(blocks):
        for _ in range(layers):
            out.append((hw, hw, 1, c, bn * k))
            out.append((hw, hw, 3, bn * k, k))
            c += k
        if b != len(blocks) - 1:
            out.append((hw, hw, 1, c, c // 2))
            c //= 2
            hw //= 2
    return out, c


def forward_flops_per_image(s: dict) -> float:
    convs, c = _convs(s)
    flops = sum(2.0 * h * w * k * k * ci * co for h, w, k, ci, co in convs)
    return flops + 2.0 * c * s["num_classes"]


def train_step_flops(s: dict) -> float:
    return 3.0 * forward_flops_per_image(s) * s["batch"]


def param_count(s: dict) -> int:
    convs, c = _convs(s)
    n = sum(k * k * ci * co for _, _, k, ci, co in convs)
    bn = 2 * s["num_init_features"]
    ch = s["num_init_features"]
    for b, layers in enumerate(s["block_config"]):
        for _ in range(layers):
            bn += 2 * ch + 2 * s["bn_size"] * s["growth_rate"]
            ch += s["growth_rate"]
        if b != len(s["block_config"]) - 1:
            bn += 2 * ch
            ch //= 2
    bn += 2 * ch
    return n + bn + c * s["num_classes"] + s["num_classes"]
