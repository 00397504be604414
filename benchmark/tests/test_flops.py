"""The analytic counts kept with the benchmark, against XLA's `cost_analysis`
of the program's own training step with the helpers off, where XLA sees every
operation."""
import json
import os

import jax
import pytest

from conftest import BENCH
from harness import traffic
from harness.manifest import load_module


def _load(name):
    cfg = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    ref = load_module(os.path.join(BENCH, "configs", cfg["reference"]), "ref")
    adapter = load_module(os.path.join(BENCH, "configs", cfg["program"]), "adapter")
    return cfg, ref, adapter


def _xla_flops(cfg, ref, adapter, batch_size):
    from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx
    kw, kb = jax.random.split(traffic.key_from_seed(1))
    batch = traffic.make_batch(cfg, {"batch": batch_size}, kb)
    with helpers_enabled_ctx(False):
        net = adapter.build(cfg, ref.init_params(cfg, kw), 1)
        return net.train_step_flops(batch[0], batch[1]) / batch_size


def test_lstm_analytic_count_matches_xla_with_helpers_off():
    cfg, ref, adapter = _load("textgen_lstm_dl4j_zoo")
    assert abs(ref.train_flops_per_token(cfg) - 5.08e6) < 0.01e6
    # XLA counts the body of a while loop once, whatever its trip count, so
    # even with the helpers off it does not see everything: of a `lax.scan`
    # over T timesteps it counts one. At T=2 it must therefore read the whole
    # analytic count less one of the two timesteps' recurrent products (at T=1
    # the zero initial state lets it fold the recurrent product away).
    cfg = dict(cfg, sequence_length=2)
    seen = ref.train_flops_per_sample(cfg) - ref.scan_kernel_flops_per_sample(cfg) / 2
    xla = _xla_flops(cfg, ref, adapter, 1024)
    # XLA also counts the gates' elementwise work (sigmoid, tanh, products)
    # and RmsProp over 0.86M parameters, spread here over 2048 tokens: a few
    # percent above the matrix products, never below them
    assert seen <= xla <= 1.08 * seen, (seen, xla)


def test_lstm_kernel_share_is_what_xla_cannot_see_on_the_chip():
    """With the fused scan kernel engaged, XLA's count for the chip's program
    was 1.514e12 per 8192x100 step (ISSUE 24, AOT compile for v5e): the
    analytic count less the kernel's share."""
    cfg, ref, _ = _load("textgen_lstm_dl4j_zoo")
    outside = (ref.train_flops_per_sample(cfg)
               - ref.scan_kernel_flops_per_sample(cfg)) * 8192
    assert abs(outside - 1.514e12) / 1.514e12 < 0.06, outside


@pytest.mark.slow
def test_resnet50_analytic_count_against_xla():
    cfg, ref, adapter = _load("resnet50_dl4j_zoo")
    analytic = ref.train_flops_per_sample(cfg)
    xla = _xla_flops(cfg, ref, adapter, 2)
    # ISSUE 24 read 6.16 GFLOP an image from XLA for the chip's compiled
    # program at batch 256. Here XLA counts the unoptimised step lowered for
    # the CPU at batch 2: it holds the elementwise work too (batch norm forward
    # and backward, ReLU, additions, pooling), RmsProp and l1/l2 over 25.6M
    # parameters shared by only 2 images (some 0.2 GFLOP an image), the
    # products with padding (0.5 GFLOP) and with the zeros a strided
    # convolution's backward pass inserts. The analytic count holds only the
    # products that convolutions and the head need: never above XLA's, and
    # within 30% of it.
    assert analytic <= xla <= 1.30 * analytic, (analytic, xla)
    assert 0.93 * 6.16e9 <= analytic <= 6.16e9, analytic
