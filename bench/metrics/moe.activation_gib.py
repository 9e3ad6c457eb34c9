"""Device memory beyond the train state: on each chip, the peak (live
buffers plus the reservation for compiled programs' temporaries, from
the device's own memory counters) less the bytes of the train state's
shards there; the fullest chip's. Activations, logits and gradients live
in this share, which the memory-reuse strategies S1-S4 act on."""
LAYER = "memory reuse (core/strategies.py)"
UNIT = "GiB"
BETTER = "lower"
MOVES = "peak_hbm_gib"
SOURCE = "device_trace"
WORKLOADS = ["train.bertl.1chip"]


def read(ctx, reduced):
    return ctx.layer["beyond_state_bytes"] / 2 ** 30
