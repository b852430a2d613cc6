"""Operations a configuration needs, computed from its layer table.

The convention of model-FLOP utilization: count the MXU work only
(convolutions and inner products; a multiply-accumulate is 2 operations, a
bias add counts as one more MAC per output), and charge the backward pass at
twice the forward (gradient with respect to the input and to the weights).
Recomputation is not counted.  The walk reads ``layers`` of the configuration
file and nothing of the program.
"""

import math


def _conv_out(size, k, stride, pad):
    return (size + 2 * pad - k) // stride + 1


def _pool_out(size, k, stride, pad):
    # Caffe pools in ceil mode and clips a last window that starts in the pad
    out = math.ceil((size + 2 * pad - k) / stride) + 1
    if pad and (out - 1) * stride >= size + pad:
        out -= 1
    return out


def forward_flops_per_image(layers, input_chw):
    """MXU operations of one forward pass of one image."""
    shapes = {"data": tuple(input_chw)}
    total = 0.0
    for row in layers:
        kind = row["type"]
        bottoms = row["bottom"] if isinstance(row["bottom"], list) else [row["bottom"]]
        c, h, w = shapes[bottoms[0]]
        if kind == "conv":
            k, s, p = row["k"], row.get("stride", 1), row.get("pad", 0)
            oh, ow = _conv_out(h, k, s, p), _conv_out(w, k, s, p)
            macs = oh * ow * row["out"] * (c // row.get("group", 1)) * k * k
            if row.get("bias", True):
                macs += row["out"] * oh * ow
            total += 2.0 * macs
            out = (row["out"], oh, ow)
        elif kind == "fc":
            macs = c * h * w * row["out"]
            if row.get("bias", True):
                macs += row["out"]
            total += 2.0 * macs
            out = (row["out"], 1, 1)
        elif kind == "pool":
            if row.get("global"):
                out = (c, 1, 1)
            else:
                k, s, p = row["k"], row.get("stride", 1), row.get("pad", 0)
                out = (c, _pool_out(h, k, s, p), _pool_out(w, k, s, p))
        elif kind in ("eltwise", "lrn"):
            out = (c, h, w)  # shape-preserving, no MXU work
        else:
            raise ValueError(f"layer {row['name']!r}: unknown type {kind!r}")
        shapes[row["name"]] = out
    return total


def train_flops_per_image(config):
    crop = config["crop"]
    return 3.0 * forward_flops_per_image(config["layers"], (3, crop, crop))
