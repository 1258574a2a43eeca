from ..config import DECODER_FAMILIES, MODEL_FAMILIES
from . import hourglass as _hourglass
from .hourglass import (
    Activation,
    Convolution,
    FusedBNAct,
    Head,
    Hourglass,
    Neck,
    Pool,
    PreLayer,
    QuantConv,
    Residual,
    SPP,
    StackedHourglass,
    STEConv,
    mish,
)


def build_model(args_or_cfg, dtype=None, **hourglass_options):
    """The model `cfg.family` names: the stacked hourglass (every option of
    `hourglass.build_model`, ref train.py:164-172 `load_network`) or a
    decoder of models/decoder.py (no reference analogue), imported only
    when asked for."""
    family = getattr(args_or_cfg, "family", "hourglass")
    if family == "hourglass":
        return _hourglass.build_model(args_or_cfg, dtype, **hourglass_options)
    if family in DECODER_FAMILIES and not hourglass_options:
        from .decoder import build_decoder
        return build_decoder(args_or_cfg, dtype)
    raise ValueError("no model of family %r takes %s (families: %s)"
                     % (family, sorted(hourglass_options) or "this config",
                        ", ".join(MODEL_FAMILIES)))


__all__ = [
    "Activation",
    "build_model",
    "Convolution",
    "FusedBNAct",
    "QuantConv",
    "Head",
    "Hourglass",
    "Neck",
    "Pool",
    "PreLayer",
    "Residual",
    "SPP",
    "StackedHourglass",
    "STEConv",
    "mish",
]
