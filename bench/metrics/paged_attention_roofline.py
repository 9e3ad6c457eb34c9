"""Roofline share of the paged-attention decode kernel: the least time
the chip needs for the live KV of every decode step in the window
(``bench/work.py``: bytes and FLOPs of the keys each active request
attends, not of the page table's width), over the summed device time of
the decode program's Pallas custom calls in the window (the only custom
calls that program runs). Which bound applies is printed on stderr."""
import sys

from bench import peaks, work

LAYER = "kernels (kernels/paged_attention)"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
WORKLOADS = ["serve.gpt3s.chat"]
PROGRAM = "jit__decode_step"    # the engine's jitted decode step


def read(ctx, reduced):
    secs = ctx.layer["seconds"]
    steps = [s for s in ctx.layer["run"]["steps"]
             if s["kind"] == "decode" and s["t0"] < secs]
    t = reduced["custom_call_s_by_program"].get(PROGRAM, 0.0)
    if not steps or t <= 0:
        return None
    m, pk = ctx.layer["model"], peaks.peaks(ctx.layer["kind"])
    flops = byts = 0
    for s in steps:
        f, b = work.paged_decode_attention(m, s["keys"], s["slots"])
        flops, byts = flops + f, byts + b
    tc, tm = flops / pk["flops_bf16"], byts / pk["hbm_bytes_per_s"]
    print(f"paged_attention_roofline: {'memory' if tm >= tc else 'compute'}"
          f"-bound ({byts:.4g} B, {flops:.4g} FLOP, kernel {t:.6g} s)",
          file=sys.stderr)
    return 100.0 * max(tc, tm) / t
