"""Share of the traced window in which no operation ran on the device;
with several chips, the idlest chip's."""
LAYER = "device"
UNIT = "%"
BETTER = "lower"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
WORKLOADS = ["train.bertl.1chip"]


def read(ctx, reduced):
    devs = reduced["devices"]
    if not devs:
        return None
    busy = min(d["busy_s"] for d in devs.values())
    return 100.0 * (1.0 - busy / reduced["window_s"])
