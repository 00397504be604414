"""Accelerated-kernel plug-ins (L2): the helper seam + Pallas TPU kernels.

Parity: ref nn/layers/LayerHelper + the cudnn helper interfaces
(ConvolutionHelper, LSTMHelper, BatchNormalizationHelper) — here a registry of
Pallas kernels that call sites reach through `helper_for`. Every registered
kernel runs by default on a TPU and never off one; `enable_helpers(True|False)`
or `helpers_enabled_ctx` overrides that both ways (ops/helpers.py).
"""
from deeplearning4j_tpu.ops.helpers import (
    enable_helpers, helper_for, register_helper, registered_helpers)
from deeplearning4j_tpu.ops import lstm_scan_fused  # registers graves_lstm_scan
from deeplearning4j_tpu.ops import flash_attention  # registers flash_attention
from deeplearning4j_tpu.ops import decode_attention  # registers the paged decode kernels
from deeplearning4j_tpu.ops import grouped_matmul  # registers grouped_matmul
from deeplearning4j_tpu.ops import hyper_connection  # registers hyper_connection
from deeplearning4j_tpu.ops import gated_delta_rule  # registers gated_delta_rule
from deeplearning4j_tpu.ops import max_pool  # registers max_pool_grad

__all__ = ["enable_helpers", "helper_for", "register_helper",
           "registered_helpers", "lstm_scan_fused", "flash_attention",
           "decode_attention", "grouped_matmul", "hyper_connection",
           "gated_delta_rule", "max_pool"]
