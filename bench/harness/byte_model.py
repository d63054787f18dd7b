"""The least HBM traffic of the BayesLR logit delta, from counts.

The analytic part of the repository's ``benchmarks/roofline.py``
(``bytes_min``: each operand read once, the output written once), applied
to the work the algorithm asked for rather than to a kernel call: every
section a chain evaluated reads its feature row (D float32) and its label
(one float32) once and writes one float32 delta. The two weight vectors
per chain and round are negligible and left out, so the count is a lower
bound on any kernel that does the same work.
"""
from __future__ import annotations

F32 = 4


def logit_delta_bytes(sections_evaluated: float, d: int) -> float:
    """Least bytes for ``sections_evaluated`` (summed over chains and
    transitions) row evaluations of width ``d``."""
    return float(sections_evaluated) * (d * F32 + F32 + F32)
