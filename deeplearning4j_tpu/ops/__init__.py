"""Accelerated-kernel plug-ins (L2): the helper seam + Pallas TPU kernels.

Parity: ref nn/layers/LayerHelper + the cudnn helper interfaces
(ConvolutionHelper, LSTMHelper, BatchNormalizationHelper) — here a registry of
Pallas kernels that call sites reach through `helper_for`, disabled by default
(XLA fusion is the baseline; enable with enable_helpers()/DL4J_TPU_HELPERS=1).
"""
from deeplearning4j_tpu.ops.helpers import (
    enable_helpers, helper_for, helpers_enabled, register_helper,
    registered_helpers)
from deeplearning4j_tpu.ops import pallas_kernels  # registers kernels on import
from deeplearning4j_tpu.ops import conv_fused  # registers conv1x1_bn_act
from deeplearning4j_tpu.ops import lstm_scan_fused  # registers graves_lstm_scan
from deeplearning4j_tpu.ops import flash_attention  # registers flash_attention
from deeplearning4j_tpu.ops import decode_attention  # registers the paged decode kernels
from deeplearning4j_tpu.ops import grouped_matmul  # registers grouped_matmul

__all__ = ["enable_helpers", "helpers_enabled", "helper_for", "register_helper",
           "registered_helpers", "pallas_kernels", "conv_fused",
           "lstm_scan_fused", "flash_attention", "decode_attention",
           "grouped_matmul"]
