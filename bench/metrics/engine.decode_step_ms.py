"""Mean host time of one decode step in the window, from
``repro_step_seconds{kind=decode}`` (its sum and count grow over the
window)."""
LAYER = "model step (models/lm.py)"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"
WORKLOADS = ["serve.gpt3s.chat"]


def read(ctx, reduced):
    run = ctx.layer["run"]
    (c0, s0), (c1, s1) = run["hist0"]["decode"], run["hist1"]["decode"]
    return 1e3 * (s1 - s0) / (c1 - c0) if c1 > c0 else None
