"""Transitions the background refresh committed during the window, with
queries running (``steps_done`` x chains over the time it was read)."""
UNIT = "transitions/s"
LAYER = "resident and pool"
MOVES = "staleness_p95_ms"
TRACED = True


def read(rec):
    return rec.get("refresh_transitions_per_s")
