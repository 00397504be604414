"""The manifest loader refuses what a run could not stand behind."""
import json
import os
import shutil

import pytest

from conftest import TINY
from harness import device
from harness.manifest import Cell, ManifestError, check_name


def test_real_manifest_names_files_that_exist():
    root = os.path.dirname(os.path.dirname(TINY.rstrip("/")).rstrip("/"))
    root = os.path.dirname(root)
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for w in manifest["workloads"]:
        cell = Cell(w["name"])
        assert cell.chips in (1, 4)
        assert cell.limits, w["name"]
        for m in cell.per_layer():
            assert hasattr(cell.reader(m["name"]), "read"), m["name"]
    for c in manifest["configs"]:
        assert os.path.isfile(os.path.join(root, c["file"]))
        cfg = json.load(open(os.path.join(root, c["file"])))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]


def test_unknown_device_kind_is_an_error():
    assert device.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in"):
        device.load_peaks("TPU v9 imaginary")


@pytest.mark.parametrize("bad", ["a b", "a,b", "a/b", "", ".hidden", "x" * 65,
                                 "café"])
def test_forbidden_names_are_refused(bad):
    with pytest.raises(ManifestError):
        check_name(bad, "name")
    with pytest.raises(ManifestError):
        Cell(bad, data_dir=TINY, manifest_path=os.path.join(TINY, "BENCHMARK.json"))


def test_cell_that_names_a_missing_file_is_refused(tmp_path):
    data = tmp_path / "tiny"
    shutil.copytree(TINY, data)
    os.remove(data / "traffic" / "loop_8x2.json")
    with pytest.raises(ManifestError, match="no file"):
        Cell("tiny_textgen_lstm.device_loop", data_dir=str(data),
             manifest_path=str(data / "BENCHMARK.json"))
    with pytest.raises(ManifestError, match="not in BENCHMARK.json"):
        Cell("no_such.cell", data_dir=str(data),
             manifest_path=str(data / "BENCHMARK.json"))
