"""Device time of the ops in the program's ``moe.expert_ffn`` scope (the
expert weights' casts and the grouped GEMMs, ``moe/experts.py``), over
device busy time in the window (``bench/program_trace.py``)."""
from bench import program_trace

LAYER = "MoE experts (moe/experts.py)"
UNIT = "%"
BETTER = "lower"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
WORKLOADS = ["serve.gpt3s.chat"]


def read(ctx, reduced):
    return program_trace.scope_share(program_trace.summary(ctx),
                                     "moe.expert_ffn")
