"""95th percentile of the evaluator's ``device_eval`` spans: the window
upload and every micro-batched evaluation of one batch."""
from bench.harness.stats import percentile

UNIT = "ms"
LAYER = "resident and pool"
MOVES = "query_p95_ms"
TRACED = True


def read(rec):
    durs = [s["dur_s"] for s in rec.get("spans", ()) if s.get("stage") == "device_eval"]
    return 1e3 * percentile(durs, 95) if durs else None
