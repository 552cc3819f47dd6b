"""Operations and compulsory bytes of a configuration, from its layer table.

FLOPs count the convolutions and fully-connected layers, two per
multiply-add.  Compulsory bytes are what any implementation must move
through HBM for one batch: the input images, every weight once, and the
output probabilities.  Both are counted from the configuration's shapes,
whatever a plan fuses, so a roofline share built on them cannot pass 100%.
"""
from __future__ import annotations

from typing import Dict

from chipbench.spec import in_dims, layer_shapes

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def macs_per_image(cfg: Dict) -> int:
    shapes, cin = layer_shapes(cfg), in_dims(cfg)
    total = 0
    for l, s, ci in zip(cfg["layers"], shapes, cin):
        if l["kind"] == "conv":
            total += s[0] * s[1] * s[2] * ci * l["kernel"] ** 2
        elif l["kind"] == "fc":
            total += ci * l["out"]
    return total


def flops_per_image(cfg: Dict) -> int:
    return 2 * macs_per_image(cfg)


def param_count(cfg: Dict) -> int:
    total = 0
    for l, ci in zip(cfg["layers"], in_dims(cfg)):
        if l["kind"] == "conv":
            total += l["out"] * ci * l["kernel"] ** 2
        elif l["kind"] == "fc":
            total += (ci + 1) * l["out"]
    return total


def compulsory_bytes(cfg: Dict, batch: int) -> int:
    """Input batch + all weights + output, in the configuration's dtype."""
    eb = DTYPE_BYTES[cfg["dtype"]]
    image = cfg["in_channels"] * cfg["image_hw"] ** 2
    return eb * (batch * image + param_count(cfg)
                 + batch * cfg["num_classes"])


def least_seconds(cfg: Dict, batch: int, peak: Dict) -> float:
    """The least time one chip could take for a batch: the larger of its
    operations over the peak rate and its compulsory bytes over HBM
    bandwidth."""
    return max(batch * flops_per_image(cfg) / peak["flops_per_s"],
               compulsory_bytes(cfg, batch) / peak["hbm_bytes_per_s"])

