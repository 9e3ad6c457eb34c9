"""Model FLOP utilisation of serving: the FLOPs the algorithm needs for
every prefill and decode token of the steps that began in the window
(``bench/work.py``), over the window times the chip's peak."""
from bench import peaks, work

LAYER = "whole served step"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p95_ms"
SOURCE = "host_clock"
WORKLOADS = ["serve.gpt3s.chat"]


def read(ctx, reduced):
    m, secs = ctx.layer["model"], ctx.layer["seconds"]
    flops = 0
    for s in ctx.layer["run"]["steps"]:
        if s["t0"] >= secs:
            continue
        if s["kind"] == "decode":
            flops += (2 * work.matmul_params_per_token(m) * s["slots"]
                      + work.attn_fwd_flops(m, s["keys"]))
        elif s["kind"] == "prefill" and "tokens" in s:
            flops += work.prefill_flops(m, s["pos0"], s["tokens"])
    if not flops:
        return None
    peak = peaks.peaks(ctx.layer["kind"])["flops_bf16"]
    return 100.0 * flops / (secs * peak)
