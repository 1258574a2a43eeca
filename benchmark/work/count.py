"""Operations and bytes the algorithm needs, from the configuration's shapes.

Independent of which implementation the program selects (`fused` Pallas or the
`xla` composition): the shapes come from the plain reference's walker
(`reference.model.layer_shapes`), the arithmetic is here.

FLOPs (multiply-add = 2) count convolutions only (they are > 99% of the
network's arithmetic; BatchNorm, activations, pooling, loss, decode and NMS
count as 0, so an `mfu` here is slightly under the true share, never over):

* forward: 2 * H_out * W_out * k * k * C_in * C_out per conv per image;
* training: forward + the input gradient + the weight gradient = 3 x forward,
  less the input gradient of the very first conv (nobody needs d loss /
  d image). Recomputed operations (`--remat`, a kernel's recompute backward)
  do not count.

Bytes of a train-mode BatchNorm(+skip add)+activation tail, in
activation-sized transfers of the compute dtype (bfloat16, 2 bytes). An
activation here (>= 4 MB a row block at the sizes trained) cannot stay on the
chip between passes, and batch statistics must be complete before anything is
normalized, so the least the algorithm can move is:

* forward: read x for the moments, read x again to normalize, write y = 3;
  with a skip = 4;
* backward: one pass for the two channel sums (read dy and x; the
  activation's mask is recomputed from x, and from the skip where there is
  one), one pass for dx (read dy and x again, write dx) = 5; with a skip, read
  it in both passes and write its gradient = 8.

These totals (8 and 12 per tail) happen to equal what the program's fused
kernels move (`epilogue.site_kernel_bytes`): they were built to this minimum.
The per-channel vectors are left out (< 0.01%). An eval tail is not counted:
with running statistics it is a per-channel affine that XLA fuses into the
convolution, a pass the program does not make.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import math

from ..reference.model import layer_shapes

ACT_BYTES = 2  # bfloat16 activations under --amp


def conv_flops_per_image(cfg: dict, imsize: int, train: bool) -> float:
    convs, _ = layer_shapes(cfg, imsize)
    total = 0.0
    for c in convs:
        fwd = 2.0 * c["out_hw"][0] * c["out_hw"][1] * c["k"] ** 2 \
            * c["cin"] * c["cout"]
        if not train:
            total += fwd
        else:
            total += fwd * (2.0 if c["first"] else 3.0)
    return total


def bn_tail_transfers(add: bool) -> int:
    return 12 if add else 8


def bn_tail_bytes_per_image(cfg: dict, imsize: int) -> float:
    _, tails = layer_shapes(cfg, imsize)
    return float(sum(math.prod(t["shape"]) * ACT_BYTES
                     * bn_tail_transfers(t["add"]) for t in tails))
