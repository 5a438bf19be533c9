"""The hybrid serving cell (``granite-4-h-small.rag``): it resolves from its
new files alone, its comparison accepts the program at toy size on the
CPU, and it rejects the program with a fault planted under the timed
path: padding folded into the SSM state, one routed pair dropped, a
multiplier left out."""
import dataclasses
import json
import shutil
import time

import pytest

from bench.drivers import serve_hybrid_lm as HY
from bench.run import ROOT, resolve

CELL = "granite-4-h-small.rag"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _run(seconds=1.0):
    _, c, config, traffic = resolve(CELL)
    return HY.run(cell=c, config=config, traffic=traffic, seed=11,
                  seconds=seconds, trace=False, rehearse=True,
                  device=DEVICE, t_start=time.perf_counter())


def test_cell_resolves_from_its_new_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    spec, cell, config, traffic = resolve(CELL, root=tmp_path)
    assert config["kind"] == "serve_hybrid_lm" and cell["chips"] == 1
    assert (tmp_path / "bench/drivers/serve_hybrid_lm.py").exists()
    # the file holds the catalog's config.json keys; two are cut
    assert config["num_hidden_layers"] == 10
    assert config["num_local_experts"] == 9
    assert config["router_width"] == 72
    assert sorted(config["reduced"]) == ["num_hidden_layers",
                                         "num_local_experts"]
    cfg = HY.arch_config(config)            # the chip's share, checked
    assert (cfg.n_layers, cfg.n_experts, cfg.router_width) == (10, 9, 72)
    assert cfg.param_count() == 2_414_688_896
    metrics = {m["name"] for m in spec["per_layer"]
               if CELL in m.get("workloads", [])}
    assert {"expert_load_imbalance.hybrid",
            "decode_hbm_roofline.hybrid"} <= metrics


def test_config_file_is_the_catalog_entry_with_its_cuts():
    cfg = json.loads((ROOT / "bench/configs/granite-4-h-small.json")
                     .read_text())
    assert cfg["layer_types"][5] == "attention"
    assert cfg["layer_types"].count("attention") == 4
    assert (cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"],
            cfg["mamba_d_state"], cfg["intermediate_size"],
            cfg["shared_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["vocab_size"]) == (4096, 128, 64, 128, 768, 1536, 10, 100352)
    assert cfg["published"]["num_local_experts"] == 72


def test_rag_accepts_the_program():
    r = _run()
    assert r.correct, r.checks
    assert r.attempted > 5 and r.failed == 0


def test_rag_rejects_padding_folded_into_the_state(monkeypatch):
    from repro.models import mamba as Mb
    monkeypatch.setattr(Mb, "_live", lambda dt, lengths: dt)
    r = _run()
    assert not r.correct
    assert r.checks["logit_gap"][0] > r.checks["logit_gap"][1]


def test_rag_rejects_a_dropped_pair(monkeypatch):
    """The routed pairs of each MoE call's first token left uncomputed
    (their gates zeroed): the served tokens leave the reference's."""
    from repro.models import moe as Moe
    orig = Moe._route

    def route(router, x2d, top_k):
        w, ids, aux = orig(router, x2d, top_k)
        return w.at[0].set(0.0), ids, aux
    monkeypatch.setattr(Moe, "_route", route)
    r = _run()
    assert not r.correct
    assert r.checks["logit_gap"][0] > r.checks["logit_gap"][1]


@pytest.mark.parametrize("multiplier", ["residual_multiplier",
                                        "attention_multiplier"])
def test_rag_rejects_a_multiplier_left_out(monkeypatch, multiplier):
    orig = HY.arch_config
    left_out = {"residual_multiplier": 1.0, "attention_multiplier": 0.0}
    monkeypatch.setattr(HY, "arch_config", lambda c: dataclasses.replace(
        orig(c), **{multiplier: left_out[multiplier]}))
    r = _run()
    assert not r.correct
    assert r.checks["logit_gap"][0] > r.checks["logit_gap"][1]
