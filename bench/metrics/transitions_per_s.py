"""Chains x ensemble steps committed by ``ResidentEnsemble.refresh`` (the
host pull of the draws included), over the window of whole blocks."""
UNIT = "transitions/s"
LAYER = None
MOVES = None
TRACED = False


def read(rec):
    if "transitions" not in rec:
        return None
    return rec["transitions"] / rec["window_s"]
