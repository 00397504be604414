"""How far the worst expert layer stood from the deployment's share of the
assignments in the last step of the window: the largest over the expert layers
of |held share - held experts / published experts|, the share from the gauges
`moe.assignments_held_share.*` that the program sets after each call from
counters its expert layers write on the device, the two counts from the
cell's configuration (`n_routed_experts` and `published.n_routed_experts`:
8 of 64, 0.125). 0 is a cut whose experts take what they would take in the
deployment; a router that walks towards the held experts or away from them
reads above it, and the routed rows, the blocks a layer walks and the step's
time follow. A configuration that holds every expert has nothing to read."""

PREFIX = "moe.assignments_held_share."


def read(run):
    from deeplearning4j_tpu import telemetry
    config = run.cell.config
    held = config.get("n_routed_experts")
    published = config.get("published", {}).get("n_routed_experts")
    if not held or not published or held >= published:
        return None
    shares = [v for name, v in telemetry.registry().snapshot().items()
              if name.startswith(PREFIX)]
    return max(abs(v - held / published) for v in shares) if shares else None
