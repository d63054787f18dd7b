"""95th percentile of ``Request.staleness_s``: how old the posterior's
newest draw was when it answered."""
from bench.harness.stats import percentile

UNIT = "ms"
LAYER = None
MOVES = None
TRACED = False


def read(rec):
    if len(rec.get("staleness_s", ())) == 0:
        return None
    return 1e3 * percentile(rec["staleness_s"], 95)
