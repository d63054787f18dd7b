"""Mean sequential-test rounds per transition over the window's refreshes
(``SubsampledMHInfo.rounds``)."""
import numpy as np

UNIT = "rounds"
LAYER = "ensemble and sequential test"
MOVES = "transitions_per_s"
TRACED = True


def read(rec):
    return float(np.mean(rec["rounds"])) if "rounds" in rec else None
