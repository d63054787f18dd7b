"""Share of the traced window in which no operation ran on the device
(1 - busy / window, from the profiler trace)."""
UNIT = "%"
LAYER = "device"
MOVES = "transitions_per_s"
TRACED = True


def read(rec):
    t = rec.get("trace")
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None
