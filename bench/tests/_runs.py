"""Drive one cell at its rehearsal size in this process, optionally with
the timed path broken underneath (see ``bench/harness/faults.py``)."""
import argparse

from bench import run as bench_run
from bench.harness import faults, spec

REFRESH_CELL = "bayeslr_d50_n12k.refresh"
SERVE_CELL = "bayeslr_d50_n12k.serve_poisson"


def rehearse(workload: str, fault: str | None = None, seed: int = 2**31 + 17,
             seconds: float = 2.0) -> dict:
    cell = spec.load_cell(workload)

    def hook(drive):
        def broken(ctx):
            with faults.planted(fault, cell.model, ctx.config, cell.traffic["kind"]):
                return drive(ctx)
        return broken

    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds, trace=0)
    return bench_run.run_cell(args, rehearse=True, driver_hook=hook)


def failed_checks(result: dict) -> list[str]:
    return [name for name, c in result["checks"].items()
            if not (isinstance(c["value"], float) and c["value"] <= c["limit"])]
