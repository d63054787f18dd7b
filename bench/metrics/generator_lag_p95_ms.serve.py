"""How late the load generator submitted: actual submit minus due time,
95th percentile, on the benchmark's own clock."""
import numpy as np

from bench.harness.stats import percentile

UNIT = "ms"
LAYER = "load generator"
MOVES = "query_p95_ms"
TRACED = True


def read(rec):
    lag = np.asarray(rec.get("generator_lag_s", ()), np.float64)
    lag = lag[np.isfinite(lag)]
    return 1e3 * percentile(lag, 95) if lag.size else None
