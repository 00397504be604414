"""The benchmark's own tests: CPU, tiny sizes, no chip. They run with
`pytest benchmark/tests` from the root of the checkout and are no part of the
repository's tier-1 suite."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=4").strip()

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest

TINY = os.path.join(HERE, "tiny")


@pytest.fixture
def tiny_cell():
    from harness.manifest import Cell

    def make(name):
        return Cell(name, data_dir=TINY,
                    manifest_path=os.path.join(TINY, "BENCHMARK.json"))
    return make


@pytest.fixture
def no_chip_check(monkeypatch):
    """Lifts the harness's look for a chip (here, in the test: `run.py` has no
    option for it) and lets the table of peaks be found."""
    from harness import device
    monkeypatch.setattr(device, "require_chip", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": max(1, chips)})
