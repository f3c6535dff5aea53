"""BENCHMARK.json against the contract's shape, and discovery by name."""
import json
import os
import re
import shutil

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units(manifest):
    assert set(manifest) == TOP_KEYS
    assert 1 <= manifest["run_seconds"] <= 51
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/")
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}
    moves = {m["name"] for m in manifest["end_to_end"]}
    layers = {m["layer"] for m in manifest["per_layer"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in moves and "\n" not in m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert layers
    assert all(NAME.match(n) for n in names)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in manifest[key]]
        assert len(got) == len(set(got)), key


def test_every_cell_finds_its_files(cells_root):
    man = harness.load_json(os.path.join(cells_root, "BENCHMARK.json"))
    for w in man["workloads"]:
        cell = harness.Cell(cells_root, w["name"])
        assert cell.per_layer and cell.end_to_end
        assert set(cell.metrics) == {m["name"] for m in cell.per_layer}
        for mod in cell.metrics.values():
            assert callable(mod.read)
        assert hasattr(cell.requests, "LIMITS")
        assert callable(cell.generator.generate)
        assert callable(cell.loop.drive)


def test_new_cell_is_found_from_added_files_alone(tmp_path, manifest):
    """A cell, configuration, traffic mix and metric added as files (and
    entries in BENCHMARK.json) are found by name; no harness file is
    edited."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    man = json.loads(json.dumps(manifest))
    conf = json.load(open(tmp_path / "bench/configs/mixed-10m.json"))
    conf["name"] = "mixed-1m"
    conf["records"] = 1000000
    json.dump(conf, open(tmp_path / "bench/configs/mixed-1m.json", "w"))
    traffic = json.load(open(tmp_path / "bench/traffic/tiles-closed96.json"))
    traffic["clients"] = 8
    json.dump(traffic, open(tmp_path / "bench/traffic/tiles-closed8.json",
                            "w"))
    (tmp_path / "bench/metrics/served_share.py").write_text(
        "def read(rec):\n    return 42.0\n")
    man["configs"].append({"name": "mixed-1m", "source": "x",
                           "file": "bench/configs/mixed-1m.json",
                           "reduced": ["records"], "why": "x"})
    man["workloads"].append({"name": "mixed1m-tiles8", "config": "mixed-1m",
                             "traffic": "tiles-closed8", "chips": 1,
                             "why": "x"})
    man["per_layer"].append({"name": "served_share", "unit": "%",
                             "better": "higher", "source": "program_counter",
                             "layer": "serving tier", "moves": "p95_ms",
                             "workloads": ["mixed1m-tiles8"]})
    json.dump(man, open(tmp_path / "BENCHMARK.json", "w"))
    cell = harness.Cell(str(tmp_path), "mixed1m-tiles8")
    assert cell.config["records"] == 1000000 and cell.traffic["clients"] == 8
    assert cell.metrics["served_share"].read({}) == 42.0
    assert "served_share" not in harness.Cell(
        str(tmp_path), "mixed10m-tiles-closed96").metrics


def test_peaks_table_by_device_kind():
    peaks = harness.load_json(os.path.join(ROOT, "bench", "peaks.json"))
    row = harness.peak_row(peaks, "TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 8.19e11 and "source" in row
    with pytest.raises(KeyError):
        harness.peak_row(peaks, "TPU v9 imaginary")
