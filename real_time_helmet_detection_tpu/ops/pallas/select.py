"""Where a kernel is chosen: the one place the package asks which backend
it runs on. The reference has no kernels of its own (its ops are torch's),
so this module has no analogue there.

Four `Config` fields pick between a Pallas kernel and its XLA
composition: `loss_kernel`, `epilogue`, `block_fuse` (`auto|fused|xla`)
and `use_pallas` (a bool: True is `auto`, False is `xla`). `auto` means
"the kernel on the chip, XLA elsewhere": off the chip a Pallas kernel only
runs under the (slow) interpreter, so nothing selects it there unless a
test or an attribution run names `fused`. The kernels' own `interpret=None`
defaults ask `on_chip()` too.
"""

from __future__ import annotations

import jax


def on_chip() -> bool:
    return jax.default_backend() == "tpu"


def choose(mode) -> str:
    """'fused' | 'xla' for one field's value."""
    if not isinstance(mode, str):  # use_pallas
        mode = "auto" if mode else "xla"
    if mode == "auto":
        return "fused" if on_chip() else "xla"
    return mode


def kernel_plan(cfg) -> dict:
    """What `cfg` selects on this backend, a layer a key: the detection
    loss (train.loss_fn), the per-conv BN tail and the residual block's
    tail (models.build_model), the peak kernel (predict.make_predict_fn).
    A missing field reads as its `Config` default."""
    return {"loss": choose(getattr(cfg, "loss_kernel", "auto")),
            "epilogue": choose(getattr(cfg, "epilogue", "auto")),
            "block_fuse": choose(getattr(cfg, "block_fuse", "auto")),
            "peak": choose(getattr(cfg, "use_pallas", True))}
