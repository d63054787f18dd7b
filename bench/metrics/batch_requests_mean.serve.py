"""Mean number of requests in the batch that served each request
(``Request.batch_size``)."""
import numpy as np

UNIT = "requests"
LAYER = "queue"
MOVES = "query_p95_ms"
TRACED = True


def read(rec):
    b = rec.get("batch_requests")
    return float(np.mean(b)) if b is not None and len(b) else None
