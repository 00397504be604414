"""The fused Graves-LSTM scan kernels' share of their roofline. Their events
are the Mosaic custom calls of the program: this configuration has no other
Pallas call. Operations and bytes are the configuration's analytic functions
(the recurrent products; the projected inputs, states and gradients that must
cross HBM at the compute type's width)."""
from harness import roofline


def is_kernel(label: str) -> bool:
    """`harness.trace.op_label` of a Mosaic call: `<name> custom-call
    tpu_custom_call` (the target is there where the event's text holds it)."""
    return " custom-call" in label and (
        label.endswith(" custom-call") or "tpu_custom_call" in label)


def read(run):
    cfg, ref = run.cell.config, run.cell.reference
    if not hasattr(ref, "scan_kernel_flops_per_sample"):
        return None
    itemsize = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    return roofline.share(run, is_kernel, ref.scan_kernel_flops_per_sample(cfg),
                          ref.scan_kernel_bytes_per_sample(cfg, itemsize),
                          "lstm_scan_roofline")
