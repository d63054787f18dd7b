"""95th percentile of the ``queue_wait`` spans that the queue's tracer
records from submit until the request is taken into a batch."""
from bench.harness.stats import percentile

UNIT = "ms"
LAYER = "queue"
MOVES = "query_p95_ms"
TRACED = True


def read(rec):
    durs = [s["dur_s"] for s in rec.get("spans", ()) if s.get("stage") == "queue_wait"]
    return 1e3 * percentile(durs, 95) if durs else None
