"""BENCHMARK.json against the benchmark's contract, and the files each cell
is found by."""
import json
import math
import re
import shutil

import pytest

from bench import harness
from bench.run import ROOT, resolve

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in SPEC["configs"]] + CELLS + \
        [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [x["name"] for x in SPEC[group]]
        assert len(ns) == len(set(ns))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(0.01 <= m["bound"] <= 0.25 for m in SPEC["end_to_end"])
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


def test_run_length_fits_a_full_check():
    rs = SPEC["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    spec, c, config, traffic = resolve(cell)
    assert c["chips"] in (1, 4)
    assert (ROOT / "bench" / "drivers" / f"{config['kind']}.py").exists()
    assert config["name"] == c["config"]
    assert "limits" in traffic
    e2e = {m["name"] for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in spec["per_layer"] if cell in m.get("workloads", [])]
    assert layer and all(m["moves"] in e2e for m in layer)


def test_four_chip_cells_are_at_most_half():
    four = sum(1 for w in SPEC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_reader_finds_nothing_in_an_empty_record(metric):
    read = harness.load_reader(metric)
    assert read({"device_kind": "TPU v5 lite"}) is None


def test_config_files_state_their_cuts():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert "assumed" in cfg and cfg["source"]


def test_new_cell_is_found_from_new_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    t = json.loads((tmp_path / "bench/traffic/join_groupby.json").read_text())
    t["select_mod"] = 3
    (tmp_path / "bench/traffic/join_groupby_mod3.json").write_text(
        json.dumps(t))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "fig4-join-200m.join_groupby_mod3",
                              "config": "fig4-join-200m",
                              "traffic": "join_groupby_mod3", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    _, cell, config, traffic = resolve("fig4-join-200m.join_groupby_mod3",
                                       root=tmp_path)
    assert traffic["select_mod"] == 3 and config["kind"] == "table_query"
    assert all(p.read_bytes() == b for p, b in before.items())


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")


def test_nearest_rank():
    xs = list(range(1, 101))
    assert harness.nearest_rank(xs, 95) == 95
    assert harness.nearest_rank(xs[:62], 95) == 59
    assert math.isnan(harness.nearest_rank([], 95))


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
