"""The benchmark's data files load and name things that exist."""
from __future__ import annotations

import glob
import json
import os
import re

import pytest

import tiny  # noqa: F401  (puts the checkout and src on sys.path)
from bench import spec

B = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_keys_and_names():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in B[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    assert len(json.dumps(B)) < 64 * 1024
    assert any(m["name"] == "setup_s" for m in B["end_to_end"])


@pytest.mark.parametrize("conf", B["configs"], ids=lambda c: c["name"])
def test_config_file_matches_program(conf):
    data = spec.config(conf["name"])
    assert conf["file"] == f"bench/configs/{conf['name']}.json"
    assert sorted(data["reduced"]) == sorted(conf["reduced"])
    assert {"source", "assumed", "deployment", "model"} <= set(data)
    spec.program_config(data)          # raises on any drift


@pytest.mark.parametrize("cell", B["workloads"], ids=lambda c: c["name"])
def test_cell_file_and_metrics(cell):
    data = spec.workload(cell["name"])
    assert data["why"] == cell["why"] and len(cell["why"]) <= 200
    assert os.path.exists(os.path.join(spec.BENCH, "drivers",
                                       data["driver"] + ".py"))
    assert cell["config"] in {c["name"] for c in B["configs"]}
    e2e = {m["name"] for m in spec.end_to_end_for(cell["name"])}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.per_layer_for(cell["name"])
    assert set(data["limits"])


@pytest.mark.parametrize("metric", B["per_layer"], ids=lambda m: m["name"])
def test_metric_module_declares_its_entry(metric):
    mod = spec.metric_module(metric["name"])
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE, mod.BETTER,
            mod.WORKLOADS) == (metric["layer"], metric["unit"],
                               metric["moves"], metric["source"],
                               metric["better"], metric["workloads"])
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert metric["moves"] in e2e
    for cell in metric["workloads"]:
        assert cell in e2e[metric["moves"]].get("workloads", [cell])
    assert callable(mod.read)


def test_every_metric_file_is_listed():
    files = {os.path.basename(p)[:-3] for p in
             glob.glob(os.path.join(spec.BENCH, "metrics", "*.py"))}
    assert files == {m["name"] for m in B["per_layer"]}


WIDTHS = re.compile(r"(^d_|_dim$|_rank$|^num_heads$|^num_kv_heads$|"
                    r"^top_k$|_factor$|^d_ff$|^d_expert$)")


@pytest.mark.parametrize("conf", B["configs"], ids=lambda c: c["name"])
def test_reduced_keys_are_no_widths_and_match_the_model(conf):
    data = spec.config(conf["name"])
    for key, change in data["reduced"].items():
        assert not WIDTHS.search(key), key
        assert data["model"][key] == change["here"] != change["published"]
    m = data["model"]
    assert m["num_heads"] * m["head_dim"] == m["d_model"]
