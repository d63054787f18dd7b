"""Device time of the sequential test (operations in the named scope
``seq_test``: the Welford merge and the t-test decision of every round)
per committed transition of the traced blocks, in microseconds."""
UNIT = "us"
LAYER = "ensemble and sequential test"
MOVES = "transitions_per_s"
TRACED = True
SCOPES = ("seq_test",)


def read(rec):
    scope_s = rec.get("trace", {}).get("scope_s", {}).get("seq_test")
    n = rec.get("traced_n_evaluated")
    if not scope_s or n is None or not n.size:
        return None
    return 1e6 * scope_s / n.size
