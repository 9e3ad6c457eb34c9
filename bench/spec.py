"""The benchmark as data: ``BENCHMARK.json`` at the checkout's root, one
file per configuration (``bench/configs``), per cell (``bench/workloads``)
and per per-layer metric (``bench/metrics``). Everything is found by the
name ``BENCHMARK.json`` gives it, so a later cell, configuration or metric
is a new file and a new entry, never an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def config(name: str) -> dict:
    return load_json(os.path.join(BENCH, "configs", name + ".json"))


def workload(name: str) -> dict:
    """The cell file, checked against its ``BENCHMARK.json`` entry."""
    entry = next((w for w in benchmark()["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}")
    cell = load_json(os.path.join(BENCH, "workloads", name + ".json"))
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(f"{name}: {key} is {cell[key]!r} in its cell "
                             f"file but {entry[key]!r} in BENCHMARK.json")
    return dict(cell, name=name)


def end_to_end_for(cell: str) -> list:
    return [m for m in benchmark()["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_for(cell: str) -> list:
    """Per-layer metrics read in this cell: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(cell)}
    return [m for m in benchmark()["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in e2e
                             else [])]


def metric_module(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file: the arch
    the file names, given the file's depth, heads, vocabulary and
    positions, then checked against every number the file states. A
    program config that differs from the file anywhere else fails here,
    before anything runs."""
    from repro.configs import get_config

    m = conf["model"]
    cfg = get_config(conf["arch"])
    cfg = dataclasses.replace(
        cfg, num_layers=m["num_layers"], vocab_size=m["vocab_size"],
        max_position=m["max_position"],
        attn=dataclasses.replace(cfg.attn, num_heads=m["num_heads"],
                                 num_kv_heads=m["num_kv_heads"],
                                 head_dim=m["head_dim"]))
    act = {"gelu": "gelu_tanh"}.get(cfg.ffn_act, cfg.ffn_act)
    have = {
        "num_layers": cfg.num_layers, "d_model": cfg.d_model,
        "num_heads": cfg.attn.num_heads, "num_kv_heads":
        cfg.attn.num_kv_heads, "head_dim": cfg.head_dim,
        "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
        "max_position": cfg.max_position, "num_experts":
        cfg.moe.num_experts, "d_expert": cfg.moe.d_expert,
        "top_k": cfg.moe.top_k, "moe_period": cfg.moe.moe_period,
        "moe_offset": cfg.moe.moe_offset,
        "capacity_factor": cfg.moe.capacity_factor,
        "aux_loss_weight": cfg.moe.aux_loss_weight,
        "z_loss_weight": cfg.moe.z_loss_weight, "norm": cfg.norm,
        "act": act, "positional": cfg.positional,
        "param_dtype": cfg.param_dtype,
        "compute_dtype": cfg.compute_dtype, "optimizer": cfg.optimizer,
        "tie_embeddings": cfg.tie_embeddings, "gated_ffn": cfg.gated_ffn,
        "qkv_bias": cfg.attn.qkv_bias,
    }
    diff = {k: (m.get(k), v) for k, v in have.items() if m.get(k) != v}
    if diff:
        raise SystemExit(f"{conf['arch']}: the program's config differs "
                         f"from the configuration file (file, program): "
                         f"{diff}")
    return cfg
