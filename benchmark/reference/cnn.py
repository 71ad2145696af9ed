"""Plain reference of DenseNet (Huang et al. 2017; torchvision's
``densenet121`` layout) at any widths.

uint8 images / 255, stem conv 7x7 s2 + BN + ReLU + max-pool 3x3 s2, dense
blocks of BN-ReLU-conv1x1(bn_size*k)-BN-ReLU-conv3x3(k) layers with
concatenated inputs, transitions BN-ReLU-conv1x1(half)-avg-pool 2x2, final
BN-ReLU-global-mean-linear, mean cross-entropy.  BatchNorm in training
mode: the batch's own per-channel mean and biased variance (eps from the
configuration), in f32.  Each dense block is one scanned, checkpointed
layer body (see ``_forward_loss``) so that the published batch fits and
compiles in seconds; BatchNorm spans the whole batch, so rows cannot go
through in blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["init_params", "make_grad_fn", "rows", "leaf_names"]


def _plan(model: dict):
    """[(name, shape)] in forward order, and the channel bookkeeping."""
    k, bn = model["growth_rate"], model["bn_size"]
    c = model["num_init_features"]
    out = [("conv0/kernel", (7, 7, 3, c)), ("norm0/scale", (c,)), ("norm0/bias", (c,))]
    blocks = model["block_config"]
    for b, layers in enumerate(blocks):
        for l in range(layers):
            p = f"denseblock{b + 1}/denselayer{l + 1}"
            out += [
                (f"{p}/norm1/scale", (c,)), (f"{p}/norm1/bias", (c,)),
                (f"{p}/conv1/kernel", (1, 1, c, bn * k)),
                (f"{p}/norm2/scale", (bn * k,)), (f"{p}/norm2/bias", (bn * k,)),
                (f"{p}/conv2/kernel", (3, 3, bn * k, k)),
            ]
            c += k
        if b != len(blocks) - 1:
            p = f"transition{b + 1}"
            out += [(f"{p}/norm/scale", (c,)), (f"{p}/norm/bias", (c,)),
                    (f"{p}/conv/kernel", (1, 1, c, c // 2))]
            c //= 2
    out += [("norm5/scale", (c,)), ("norm5/bias", (c,)),
            ("classifier/kernel", (c, model["num_classes"])),
            ("classifier/bias", (model["num_classes"],))]
    return out


def leaf_names(model: dict) -> list[str]:
    return [n for n, _ in _plan(model)]


def init_params(key, model: dict) -> dict:
    """He-normal convolutions (torchvision's kaiming_normal_), BatchNorm
    scales 1 + 0.1 n and biases 0.1 n, a lecun-normal classifier."""
    out = {}
    for i, (name, shape) in enumerate(_plan(model)):
        n = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("scale"):
            out[name] = 1.0 + 0.1 * n
        elif name.endswith("bias"):
            out[name] = 0.1 * n
        elif name == "classifier/kernel":
            out[name] = n / jnp.sqrt(jnp.float32(shape[0]))
        else:
            fan_in = shape[0] * shape[1] * shape[2]
            out[name] = n * jnp.sqrt(2.0 / fan_in)
    return out


def rows(batch) -> int:
    return int(batch[0].shape[0])


def _forward_loss(params, images, labels, model, precision):
    cast, prec, round_back = precision
    eps = model.get("bn_eps", 1e-5)

    def conv(x, w, stride=1, pad=0):
        return round_back(jax.lax.conv_general_dilated(
            cast(x), cast(w), (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
            preferred_element_type=jnp.float32,
        ))

    def bn_relu(x, scale, bias):
        x = x.astype(jnp.float32)
        mu = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mu), axis=(0, 1, 2))
        return jax.nn.relu((x - mu) * jax.lax.rsqrt(var + eps) * scale + bias)

    def named_bn_relu(x, prefix):
        return bn_relu(x, params[f"{prefix}/scale"], params[f"{prefix}/bias"])

    x = images.astype(jnp.float32) / 255.0
    x = named_bn_relu(conv(x, params["conv0/kernel"], 2, 3), "norm0")
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)],
    )
    blocks = model["block_config"]
    k, width = model["growth_rate"], model["bn_size"] * model["growth_rate"]
    for b, layers in enumerate(blocks):
        # One scanned, checkpointed body per block, so that the whole net
        # is a handful of small programs and not 58 unrolled layers.  The
        # block's features live in one buffer of the block's final width;
        # a layer reads all of it through BatchNorm parameters and 1x1
        # kernel rows that are ZERO beyond the channels written so far
        # (an unwritten channel is 0, normalises to relu(0) = 0 and meets
        # a zero kernel row), which is the concatenation, to the bit.
        c0 = x.shape[-1]
        total = c0 + layers * k
        buf = jnp.pad(x, [(0, 0), (0, 0), (0, 0), (0, layers * k)])

        def padded(name, axis):
            rows = []
            for l in range(layers):
                w = params[f"denseblock{b + 1}/denselayer{l + 1}/{name}"]
                pad = [(0, 0)] * w.ndim
                pad[axis] = (0, total - w.shape[axis])
                rows.append(jnp.pad(w, pad))
            return jnp.stack(rows)

        def stacked(name):
            return jnp.stack([
                params[f"denseblock{b + 1}/denselayer{l + 1}/{name}"] for l in range(layers)
            ])

        per_layer = {
            "s1": padded("norm1/scale", 0), "b1": padded("norm1/bias", 0),
            "w1": padded("conv1/kernel", 2),
            "s2": stacked("norm2/scale"), "b2": stacked("norm2/bias"),
            "w2": stacked("conv2/kernel"),
            "at": c0 + k * jnp.arange(layers),
        }

        @jax.checkpoint
        def layer(buf, p):
            h = bn_relu(buf, p["s1"], p["b1"])
            h = bn_relu(conv(h, p["w1"]), p["s2"], p["b2"])
            new = conv(h, p["w2"], 1, 1)
            return jax.lax.dynamic_update_slice_in_dim(buf, new, p["at"], axis=3), None

        # the block as a whole is checkpointed too: its per-layer buffers
        # then live only while that block's backward runs
        x = jax.checkpoint(lambda buf, pl: jax.lax.scan(layer, buf, pl)[0])(buf, per_layer)
        if b != len(blocks) - 1:
            p = f"transition{b + 1}"
            x = conv(named_bn_relu(x, f"{p}/norm"), params[f"{p}/conv/kernel"])
            n, h, w, c = x.shape
            x = x.reshape(n, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))
    x = named_bn_relu(x, "norm5").mean(axis=(1, 2))
    logits = round_back(jnp.einsum(
        "nc,ck->nk", cast(x), cast(params["classifier/kernel"]),
        precision=prec, preferred_element_type=jnp.float32))
    logits = logits + params["classifier/bias"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def make_grad_fn(model: dict, precision, row_block: int = 0):
    """``(params, (images, labels)) -> (mean loss, grads)``.  ``row_block``
    is not used: batch statistics span the batch."""

    def grad_fn(params, batch):
        images = jnp.asarray(batch[0], jnp.uint8)
        labels = jnp.asarray(batch[1], jnp.int32)
        return jax.value_and_grad(
            lambda p: _forward_loss(p, images, labels, model, precision)
        )(params)

    return grad_fn
