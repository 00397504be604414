"""How uneven the held experts' load was in the last step of the window: the
largest over the expert layers of (tokens the busiest held expert took) over
(the held experts' mean), from the gauges `moe.expert_load.max_over_mean.*`
that the program sets after each call from counters its expert layers write
on the device. 1 is even; the grouped product's work follows the sum, its
tiles the unevenness."""


def read(run):
    from deeplearning4j_tpu import telemetry
    values = [v for name, v in telemetry.registry().snapshot().items()
              if name.startswith("moe.expert_load.max_over_mean.")]
    return max(values) if values else None
