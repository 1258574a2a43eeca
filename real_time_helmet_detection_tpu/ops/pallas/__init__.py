"""Pallas TPU kernels for the detection hot paths."""

from .epilogue import FUSED_EPILOGUE_ACTIVATIONS, fused_bn_act
from .loss import fused_detection_loss, fused_stack_loss_sums
from .peak import fused_peak_scores, peak_scores_reference

__all__ = ["FUSED_EPILOGUE_ACTIVATIONS", "fused_bn_act",
           "fused_detection_loss", "fused_stack_loss_sums",
           "fused_peak_scores", "peak_scores_reference"]
