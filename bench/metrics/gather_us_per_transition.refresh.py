"""Device time of the round's gathers (operations in the named scope
``gather``: ``core/target_builder._gather``, the label and row gathers of
every sequential-test round) per committed transition of the traced
blocks (chains x steps x blocks), in microseconds."""
UNIT = "us"
LAYER = "target family"
MOVES = "transitions_per_s"
TRACED = True
SCOPES = ("gather",)


def read(rec):
    scope_s = rec.get("trace", {}).get("scope_s", {}).get("gather")
    n = rec.get("traced_n_evaluated")
    if not scope_s or n is None or not n.size:
        return None
    return 1e6 * scope_s / n.size
