"""Device idle time in the window put down to a span of the engine's
host loop: each idle gap goes to the innermost ``engine.*`` phase span or
``host.gc`` pause around its middle (``bench/program_trace.py``); the sum,
over the window. The split by span is printed on stderr."""
from bench import program_trace

LAYER = "serving engine (serve/engine.py)"
UNIT = "%"
BETTER = "lower"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
WORKLOADS = ["serve.gpt3s.chat"]


def read(ctx, reduced):
    return program_trace.host_gap_share(program_trace.summary(ctx))
