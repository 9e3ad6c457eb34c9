"""Share of the window the engine spent in prefill steps: the growth of
``repro_step_seconds{kind=prefill}``'s sum over the window, over the
window."""
LAYER = "serving engine (serve/engine.py)"
UNIT = "%"
BETTER = "lower"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"
WORKLOADS = ["serve.gpt3s.chat"]


def read(ctx, reduced):
    run = ctx.layer["run"]
    spent = run["hist1"]["prefill"][1] - run["hist0"]["prefill"][1]
    return 100.0 * spent / ctx.layer["seconds"]
