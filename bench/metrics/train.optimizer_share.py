"""Device time of the ops in the program's ``optimizer`` scope (AdamW's
update, ``optim/adamw.py``), over device busy time in the window, summed
over the chips (``bench/program_trace.py``)."""
from bench import program_trace

LAYER = "optimizer (optim/adamw.py)"
UNIT = "%"
BETTER = "lower"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
WORKLOADS = ["train.bertl.1chip"]


def read(ctx, reduced):
    return program_trace.scope_share(program_trace.summary(ctx),
                                     "optimizer")
