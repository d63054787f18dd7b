"""95th percentile over every request due in the window, each timed from
its due time to its result; a failed or unfinished request is infinite."""
from bench.harness.stats import percentile

UNIT = "ms"
LAYER = None
MOVES = None
TRACED = False


def read(rec):
    if "latency_s" not in rec:
        return None
    return 1e3 * percentile(rec["latency_s"], 95)
