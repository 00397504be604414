"""The gated delta rule's share of its roofline: the least time the chip
could take for the recurrence of every DeltaNet layer, forward and backward
(the configuration's analytic operations of the chunked form and the bytes q,
k, v, the gates, the output and their gradients must cross HBM: counts of the
algorithm's required work, so that the number reads the same work whatever
implements it), over the device time of the operations under the layers'
`delta_rule` scope. Recomputed work is in the time and not in the counts."""
from harness import program_trace


def read(run):
    cfg, ref = run.cell.config, run.cell.reference
    if not hasattr(ref, "delta_rule_flops_per_sample"):
        return None
    p, table = program_trace.of(run), program_trace.scopes(run)
    if p is None or table is None:
        return None
    ns = 0
    for name, start, end in p.train_program_ops():
        op_name = table.get(name, "")
        if "dl4j.GatedDeltaNet/" in op_name and "/delta_rule/" in op_name + "/":
            ns += end - start
    if ns <= 0:
        return None
    samples = p.steps * int(run.cell.traffic["batch"])
    itemsize = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    least_s = max(
        ref.delta_rule_flops_per_sample(cfg) * samples
        / run.peaks["bf16_flops_per_s"],
        ref.delta_rule_bytes_per_sample(cfg, itemsize) * samples
        / run.peaks["hbm_bytes_per_s"])
    value = 100.0 * least_s / (ns / 1e9)
    if value > 100.0:
        raise ValueError(
            f"gated_delta_rule_roofline reads {value:.1f}% of its roofline: its "
            "operations or bytes are counted too high, or the scope leaves out "
            "part of the work")
    return value
