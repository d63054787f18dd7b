"""Set-up time: process start to the first timed step (data made on the
device, the pool built, every program compiled or loaded from the cache,
autotune, warm-up)."""
UNIT = "s"
LAYER = None
MOVES = None
TRACED = False


def read(rec):
    return rec["setup_s"]
