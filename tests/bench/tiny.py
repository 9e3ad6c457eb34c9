"""A tiny member of the MoE decoder family, for the harness's CPU tests:
the configuration-file ``model`` object, the program config that matches
it, and a harness ``Context`` for a cell at that size."""
from __future__ import annotations

import copy
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

MODEL = {
    "family": "moe_decoder", "num_layers": 2, "d_model": 64,
    "num_heads": 4, "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
    "num_experts": 4, "d_expert": 32, "top_k": 1, "moe_period": 2,
    "moe_offset": 1, "capacity_factor": 1.25, "capacity_multiple": 8,
    "aux_loss_weight": 0.01, "z_loss_weight": 0.001, "vocab_size": 256,
    "max_position": 512, "positional": "learned", "norm": "layernorm",
    "norm_eps": 1e-06, "act": "gelu_tanh", "gated_ffn": False,
    "qkv_bias": False, "tie_embeddings": False, "param_dtype": "float32",
    "compute_dtype": "bfloat16", "optimizer": "adamw",
}


# Limits of ``correct`` at this size, set as the cells' are (above the
# program's readings, below the fp8 control's), from CPU readings over
# five seeds: program at most 0.0024 / 0.0037 / 0.0027 (loss, grad,
# change gaps), fp8 control at least 0.0080 / 0.0125 / 0.0047, half the
# batch left out at least 0.052 / 0.46 / 0.18; served gap (four seeds):
# program at most 0.009, control at least 0.054. The change gap does not
# separate the control at this size; the loss and gradient gaps do.
LIMITS = {"train": {"loss_gap": 0.005, "grad_gap": 0.008,
                    "delta_gap": 0.006},
          "serve": {"served_gap": 0.02}}


def program_config(conf):
    from repro.configs import get_config

    m = conf["model"]
    cfg = get_config("moe-gpt3-s")
    return dataclasses.replace(
        cfg, name="moe-tiny", num_layers=m["num_layers"],
        d_model=m["d_model"], d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        max_position=m["max_position"],
        attn=dataclasses.replace(cfg.attn, num_heads=m["num_heads"],
                                 num_kv_heads=m["num_kv_heads"],
                                 head_dim=m["head_dim"]),
        moe=dataclasses.replace(cfg.moe, num_experts=m["num_experts"],
                                d_expert=m["d_expert"]))


def context(cell_name: str, seed: int = 1, seconds: float = 1.0,
            trace: bool = False, **params):
    """A Context for ``cell_name``'s file at the tiny size; ``params``
    override the cell's traffic parameters."""
    from bench import spec
    from bench.run import Context

    cell = dict(spec.workload(cell_name))
    cell["params"] = dict(copy.deepcopy(cell["params"]), **params)
    cell["limits"] = dict(LIMITS[cell["driver"]])
    conf = {"arch": "moe-tiny", "model": dict(MODEL)}
    return Context(cell, conf, seed, seconds, trace)
