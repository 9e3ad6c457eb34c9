"""Model FLOP utilisation of training: the FLOPs a token needs forward
and backward (``bench/work.py``; recomputation not counted) times the
window's tokens per second, over the chips' summed peak."""
from bench import peaks, work

LAYER = "train step (runtime/train_loop.py)"
UNIT = "%"
BETTER = "higher"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"
WORKLOADS = ["train.bertl.1chip"]


def read(ctx, reduced):
    lay = ctx.layer
    peak = peaks.peaks(lay["kind"])["flops_bf16"] * lay["chips"]
    per_token = work.train_flops_per_token(lay["model"], lay["seq"])
    return 100.0 * per_token * lay["tokens_per_s"] / peak
