"""Median, over the evaluator's ``repro.device_eval.run`` annotations in the
traced window, of the time from the annotation's start to the start of
the first device operation in the named scope ``query_eval`` after it:
how long a dispatched query waits for the chip, in milliseconds."""
import statistics

UNIT = "ms"
LAYER = "resident and pool"
MOVES = "query_p95_ms"
TRACED = True
SPANS = ("repro.device_eval.run",)
SCOPES = ("query_eval",)


def read(rec):
    delays = (rec.get("trace", {}).get("first_op_delay_s", {})
              .get("repro.device_eval.run", {}).get("query_eval", ()))
    delays = [d for d in delays if d is not None]
    return 1e3 * statistics.median(delays) if delays else None
