"""chip_smoke.py's control flow, checked where there is no chip.

The smoke itself only means something on the TPU (and refuses to run
anywhere else — first test). What tier-1 can hold is everything around the
device: the parent never touches JAX, importing the package starts no
backend, the compile cache goes where the rule says, a failed phase fails
the run, and each phase function runs end to end at a tiny size with the
kernels in interpret mode.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _python(code, **env):
    """Run `code` in a fresh interpreter at the repo root, on the CPU."""
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update({"JAX_PLATFORMS": "cpu", **env})
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=full,
                          capture_output=True, text=True, timeout=120)


def test_without_a_chip_it_exits_nonzero_at_once_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == chip_smoke.EXIT_NO_ACCELERATOR
    assert "platform 'cpu'" in p.stderr and "platform=cpu" in p.stdout
    assert "jax=" in p.stdout and "libtpu=" in p.stdout
    # no result and no rate; only the first phase was tried
    assert '"ok"' not in p.stdout and "/s" not in p.stdout
    assert "train_attention" not in p.stdout


_FAKE_CHILD = """
import json, sys
import chip_smoke
device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
cache = {"dir": "d", "placed_by": "code", "compiles": 3, "hits": 1,
         "misses": 2}
failing = sys.argv[1:]
def fake(phase, budget_s):
    if phase in failing:
        return 1, None
    return 0, {"phase": phase, "device": device, "result": {},
               "wall_s": 0.0, "compile_cache": cache}
chip_smoke._run_child = fake
code = chip_smoke.main(["chip_smoke.py"])
assert "jax" not in sys.modules and "deeplearning4j_tpu" not in sys.modules
sys.exit(code)
"""


def test_parent_stays_off_jax_and_ends_with_the_result_line():
    p = subprocess.run([sys.executable, "-c", _FAKE_CHILD], cwd=ROOT,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_one_failed_phase_fails_the_run_after_the_others_ran():
    p = subprocess.run([sys.executable, "-c", _FAKE_CHILD, "train_attention"],
                       cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert p.returncode == 1
    assert '"ok"' not in p.stdout
    assert "[serve] ok" in p.stdout          # later phases still ran
    assert "FAILED phases: ['train_attention']" in p.stderr


def test_a_child_is_killed_at_its_budget(monkeypatch):
    """_run_child's own machinery, with a sleeping stand-in for the phase:
    the child's process group is killed when its time is up."""
    import time
    real_popen = subprocess.Popen
    monkeypatch.setattr(
        chip_smoke.subprocess, "Popen",
        lambda cmd, **kw: real_popen(
            [sys.executable, "-c", "import time; time.sleep(60)"], **kw))
    t0 = time.monotonic()
    code, result = chip_smoke._run_child("any", budget_s=0.5)
    assert code != 0 and result is None
    assert time.monotonic() - t0 < 30


def test_importing_the_package_initialises_no_backend():
    p = _python(
        "import deeplearning4j_tpu, deeplearning4j_tpu.serving, "
        "deeplearning4j_tpu.parallel, deeplearning4j_tpu.models, "
        "deeplearning4j_tpu.telemetry, deeplearning4j_tpu.ops\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()")
    assert p.returncode == 0, p.stderr


_CACHE = """
import jax
from deeplearning4j_tpu.util.compile_cache import configure_compile_cache
print(configure_compile_cache())
print(jax.config.jax_compilation_cache_dir)
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized()
"""


def test_compile_cache_goes_to_a_fixed_ignored_directory_in_the_checkout():
    p = _python(_CACHE)
    assert p.returncode == 0, p.stderr
    placed, in_config = p.stdout.split()
    assert placed == in_config == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    p = _python(_CACHE, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert p.returncode == 0, p.stderr
    placed, in_config = p.stdout.split()
    assert placed == "None" and in_config == str(tmp_path)


# ---- each phase at a tiny size, kernels in interpret mode
def test_phase_train_attention_tiny():
    out = chip_smoke.phase_train_attention(
        batch=2, seq_len=64, d_model=32, heads=2, block_size=16, window=16,
        steps=2)
    assert set(out) == {"attention window=0", "attention window=16"}
    assert all(len(v["losses"]) == 4 for v in out.values())


def test_phase_train_graves_lstm_tiny():
    out = chip_smoke.phase_train_graves_lstm(batch=8, seq_len=4, steps=2)
    assert len(out["losses"]) == 4 and out["first_loss_helpers_off"] > 0


@pytest.mark.slow   # ~45 s: five compiles of the decoder on the CPU
def test_phase_train_decoder_tiny():
    # float32: the CPU's runtime has no bf16 x bf16 -> f32 product
    out = chip_smoke.phase_train_decoder(
        seq_len=128, hidden=128, heads=2, experts=4, vocab=64, steps=1,
        sinkhorn_rounds=2, compute_dtype="float32")
    assert len(out["losses"]) == 2
    assert out["seam"]["kernel"] >= 6 and out["seam"]["fallback"] == 6


def test_phase_delta_net_tiny():
    # float32: the CPU's runtime has no bf16 x bf16 -> f32 product
    out = chip_smoke.phase_delta_net(batch=2, seq_len=40, hidden=16, k_heads=2,
                                     v_heads=4, width=8,
                                     compute_dtype="float32", second=None)
    assert out["seam"] == {"kernel": 1, "fallback": 0}
    assert out["farthest"][1] <= 1e-4


def test_phase_delta_net_tiny_at_the_second_shape():
    """The Olmo-Hybrid share's shape cut small: one value head a key head, 12
    against 24 wide, the write strength up to 2."""
    out = chip_smoke.phase_delta_net(batch=1, seq_len=40, hidden=16, k_heads=2,
                                     v_heads=2, width=8, compute_dtype="float32",
                                     second=(24, 3, 12, 24))
    assert out["second"]["seam"] == {"kernel": 1, "fallback": 0}
    assert out["second"]["farthest"][1] <= 1e-4


def test_phase_serve_tiny():
    out = chip_smoke.phase_serve(
        d_model=32, heads=4, kv_heads=2, vocab=16, max_seqs=4, max_len=64,
        prompt_len=20, new_tokens=6, n_requests=4)
    assert out["seam"]["kernel"] > 0 and out["seam"]["fallback"] == 0


def test_phase_multichip_says_what_it_saw_and_does_not_run(capsys):
    import jax
    n = len(jax.devices())
    out = chip_smoke.phase_multichip(chips=n + 1)
    assert "skipped" in out
    assert f"saw {n} device(s)" in capsys.readouterr().out


@pytest.mark.slow   # ~70 s: XLA's CPU compile of the ResNet50 backward
def test_phase_train_resnet50_tiny():
    out = chip_smoke.phase_train_resnet50(batch=2, image=32, classes=10,
                                          steps=2)
    assert len(out["losses"]) == 4
