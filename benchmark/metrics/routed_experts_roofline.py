"""The held experts' products' share of their roofline: the least time the
chip could take for the three grouped products of every expert layer, forward
and backward (the configuration's analytic operations and bytes, the
assignments taken as even over the published experts), over the device time
of the operations under the expert layers' `routed` scope: the sort, the
gather of the routed rows and the scatter back are in that time, so that it
reads the same work whatever implements it. Recomputed products are in the
time and not in the operations."""
from harness import program_trace


def read(run):
    cfg, ref = run.cell.config, run.cell.reference
    if not hasattr(ref, "routed_products_flops_per_sample"):
        return None
    p, table = program_trace.of(run), program_trace.scopes(run)
    if p is None or table is None:
        return None
    ns = 0
    for name, start, end in p.train_program_ops():
        op_name = table.get(name, "")
        if "dl4j.RoutedExperts/" in op_name and "/routed/" in op_name + "/":
            ns += end - start
    if ns <= 0:
        return None
    samples = p.steps * int(run.cell.traffic["batch"])
    itemsize = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    least_s = max(
        ref.routed_products_flops_per_sample(cfg) * samples
        / run.peaks["bf16_flops_per_s"],
        ref.routed_products_bytes_per_sample(cfg, itemsize) * samples
        / run.peaks["hbm_bytes_per_s"])
    value = 100.0 * least_s / (ns / 1e9)
    if value > 100.0:
        raise ValueError(
            f"routed_experts_roofline reads {value:.1f}% of its roofline: its "
            "operations or bytes are counted too high, or the scope leaves out "
            "part of the work")
    return value
