"""Share of its HBM roofline that the logit delta kernel reached.

Numerator: the least HBM time of the work the traced blocks' sequential
tests asked for, the bytes of every section evaluated (``byte_model``)
over the chip's peak bandwidth. Denominator: the device time of the kernel's
operations in the trace, found by name. The work is counted from the
algorithm, so a later kernel doing the same work reads on the same
yardstick. Nothing is returned where the trace holds no such operation.
"""
import numpy as np

from bench.harness.byte_model import logit_delta_bytes
from bench.harness.peaks import peaks_for

UNIT = "%"
LAYER = "kernels"
MOVES = "transitions_per_s"
TRACED = True
KERNELS = ("batched_logit_delta",)


def read(rec):
    kernel_s = rec.get("trace", {}).get("kernel_s", {}).get("logit_delta_roofline", 0.0)
    if not kernel_s or "traced_n_evaluated" not in rec:
        return None
    least_bytes = logit_delta_bytes(float(np.sum(rec["traced_n_evaluated"])), rec["width"])
    least_s = least_bytes / peaks_for(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
