"""Device time of the ops in the program's ``sample`` scope (the whole
sampler, ``serve/sampling.py``), over device busy time in the window
(``bench/program_trace.py``)."""
from bench import program_trace

LAYER = "sampler (serve/sampling.py)"
UNIT = "%"
BETTER = "lower"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
WORKLOADS = ["serve.gpt3s.chat"]


def read(ctx, reduced):
    return program_trace.scope_share(program_trace.summary(ctx),
                                     "sample")
