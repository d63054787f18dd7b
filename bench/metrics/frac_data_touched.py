"""Sections evaluated per transition over N (``SubsampledMHInfo
.n_evaluated``), over the window's refreshes."""
import numpy as np

UNIT = "fraction"
LAYER = "ensemble and sequential test"
MOVES = "transitions_per_s"
TRACED = True


def read(rec):
    if "n_evaluated" not in rec:
        return None
    return float(np.mean(rec["n_evaluated"])) / rec["num_sections"]
