"""Share of the traced window in which no operation ran on the device."""
LAYER = "device"
UNIT = "%"
BETTER = "lower"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
WORKLOADS = ["serve.gpt3s.chat"]


def read(ctx, reduced):
    if not reduced["devices"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
