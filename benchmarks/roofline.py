"""Per-kernel roofline harness over the repro.kernels dispatch surface.

For each production kernel (the five ``repro.kernels.ops`` entry points:
pair-fused logit delta, its ensemble-batched form, the batched AR(1)
transition delta, fused CE, and its ensemble-batched form) this times the
``mode="auto"`` dispatch path — exactly what the samplers execute: the
Pallas kernel on TPU, the jnp reference elsewhere — and pairs the measured
wall time with the kernel's analytic operation/byte model:

  * ``flops``            analytic FLOPs per call
  * ``bytes_min``        compulsory HBM traffic (each operand read once,
                         the output written once) — the fused kernels'
                         design point
  * ``intensity``        flops / bytes_min (arithmetic intensity)
  * ``gflops`` /``gbs``  achieved rates from the measured wall time
  * ``tpu_bound``        which side of the TPU-v5e roofline the analytic
                         model puts the kernel on (compute vs memory), with
                         the corresponding ideal per-call seconds

The wall time is the host's clock on whatever backend runs this; no share
of a device peak is derived from it (a kernel's share of its roofline on
the chip is read from the device trace by ``bench/``).

Each bytes-bound family also runs a ``*_bf16`` variant (the
``precision="bf16"`` data path: gathered slabs and matmul operands in
bfloat16, fp32 accumulation) whose analytic ``bytes_min`` reflects the
halved slab traffic, and the fused particle-Gibbs sweep
(``repro.kernels.pgibbs``) is modeled as one time-major scan over the
(K, S, P) particle block.

The machine-readable result lands in ``BENCH_roofline.json`` (see
``multichain_bench.bench_json_path``) next to the other bench artifacts so
``benchmarks/gate.py`` can diff per-kernel throughput run-over-run.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops

from .multichain_bench import bench_json_path

# TPU v5e single-chip peaks — the roofline the kernels were designed
# against; on CPU the measured rates land far below, but the analytic
# bound classification is machine-independent.
PEAK_FLOPS = 197e12  # bf16 FLOP/s
HBM_BW = 819e9       # B/s


def _time(f, *args, n: int = 5) -> float:
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def _nbytes(*arrays) -> int:
    return sum(a.size * a.dtype.itemsize for a in arrays)


def _case_logit_delta(n: int, d: int):
    x = jax.random.normal(jax.random.key(0), (n, d))
    y = jnp.where(jax.random.bernoulli(jax.random.key(1), 0.5, (n,)), 1.0, -1.0)
    w1 = jax.random.normal(jax.random.key(2), (d,))
    w2 = jax.random.normal(jax.random.key(3), (d,))
    args = (x, y, w1, w2)
    out_b = n * 4
    return {
        "name": f"logit_delta_N{n}_D{d}",
        "fn": ops.logit_delta,
        "args": args,
        # two matvecs (2ND each) + ~8 elementwise ops per row
        "flops": 2 * 2.0 * n * d + 8.0 * n,
        "bytes_min": _nbytes(*args) + out_b,
        "shape": f"N={n} D={d}",
    }


def _case_batched_logit_delta(k: int, m: int, d: int, precision: str = "fp32"):
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    xg = jax.random.normal(jax.random.key(0), (k, m, d), dt)
    yg = jnp.where(jax.random.bernoulli(jax.random.key(1), 0.5, (k, m)), 1.0, -1.0)
    w1 = jax.random.normal(jax.random.key(2), (k, d), dt)
    w2 = jax.random.normal(jax.random.key(3), (k, d), dt)
    args = (xg, yg, w1, w2)
    suffix = "_bf16" if precision == "bf16" else ""
    return {
        "name": f"batched_logit_delta_K{k}_m{m}_D{d}{suffix}",
        "fn": ops.batched_logit_delta,
        "args": args,
        "kw": {"mode": "auto", "precision": precision},
        "precision": precision,
        "flops": 2 * 2.0 * k * m * d + 8.0 * k * m,
        "bytes_min": _nbytes(*args) + k * m * 4,
        "shape": f"K={k} m={m} D={d}",
    }


def _case_ar1_delta(k: int, m: int, precision: str = "fp32"):
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    keys = jax.random.split(jax.random.key(0), 6)
    xt = jax.random.normal(keys[0], (k, m), dt)
    xp = jax.random.normal(keys[1], (k, m), dt)
    phi1 = 0.9 * jnp.tanh(jax.random.normal(keys[2], (k,)))
    phi2 = 0.9 * jnp.tanh(jax.random.normal(keys[3], (k,)))
    s21 = jnp.exp(jax.random.normal(keys[4], (k,)))
    s22 = jnp.exp(jax.random.normal(keys[5], (k,)))
    args = (xt, xp, phi1, s21, phi2, s22)
    suffix = "_bf16" if precision == "bf16" else ""
    return {
        "name": f"ar1_delta_K{k}_m{m}{suffix}",
        "fn": ops.batched_gaussian_ar1_delta,
        "args": args,
        "kw": {"mode": "auto", "precision": precision},
        "precision": precision,
        # per (k, m) element: two gaussian logpdfs, ~10 flops each
        "flops": 20.0 * k * m,
        "bytes_min": _nbytes(*args) + k * m * 4,
        "shape": f"K={k} m={m}",
    }


def _case_fused_ce(t: int, d: int, v: int, precision: str = "bf16"):
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    h = jax.random.normal(jax.random.key(0), (t, d), dt)
    tab = jax.random.normal(jax.random.key(1), (v, d), dt)
    tgt = jax.random.randint(jax.random.key(2), (t,), 0, v)
    args = (h, tab, tgt)
    suffix = "_fp32" if precision == "fp32" else ""
    return {
        "name": f"fused_ce_T{t}_D{d}_V{v}{suffix}",
        "fn": ops.fused_ce,
        "args": args,
        "kw": {"mode": "auto", "precision": precision},
        "precision": precision,
        # logits matmul + logsumexp over V per token
        "flops": 2.0 * t * d * v + 3.0 * t * v,
        "bytes_min": _nbytes(*args) + t * 4,
        "shape": f"T={t} D={d} V={v}",
        # what the fused kernel avoids: materializing (T, V) f32 logits
        "naive_bytes": _nbytes(*args) + t * 4 + 2 * t * v * 4,
    }


def _case_pgibbs_sweep(k: int, s: int, t: int, p: int):
    from repro.kernels.pgibbs import batched_pgibbs_sweep

    keys = jax.random.split(jax.random.key(0), k)
    obs = jax.random.normal(jax.random.key(1), (s, t))
    h = jax.random.normal(jax.random.key(2), (k, s, t)) * 0.1
    phi = jnp.full((k,), 0.95)
    s2 = jnp.full((k,), 0.02)
    args = (keys, obs, h, phi, s2)
    # per (chain, series, particle, step): AR(1) propagate (~4 flops incl.
    # the normal draw's transform), obs logpdf (~10 with the exp), softmax+
    # cumsum amortized (~3), inverse-CDF resample (~log2 P)
    import math

    flops = k * s * p * t * (4 + 10 + 3 + math.log2(max(p, 2)))
    # compulsory traffic: obs read, reference paths read, trajectory written;
    # the per-step particle block lives on chip inside the scan
    bytes_min = (s * t + 2 * k * s * t) * 4
    return {
        "name": f"pgibbs_sweep_K{k}_S{s}_T{t}_P{p}",
        "fn": batched_pgibbs_sweep,
        "args": args,
        "kw": {"num_particles": p, "mode": "fast"},
        "path": "fused-scan",
        "flops": flops,
        "bytes_min": bytes_min,
        "shape": f"K={k} S={s} T={t} P={p}",
    }


def _case_batched_fused_ce(k: int, t: int, d: int, v: int):
    h = jax.random.normal(jax.random.key(0), (k, t, d), jnp.bfloat16)
    tab = jax.random.normal(jax.random.key(1), (v, d), jnp.bfloat16)
    tgt = jax.random.randint(jax.random.key(2), (k, t), 0, v)
    args = (h, tab, tgt)
    return {
        "name": f"batched_fused_ce_K{k}_T{t}_V{v}",
        "fn": ops.batched_fused_ce,
        "args": args,
        "kw": {"mode": "auto", "precision": "bf16"},
        "precision": "bf16",
        "flops": 2.0 * k * t * d * v + 3.0 * k * t * v,
        "bytes_min": _nbytes(*args) + k * t * 4,
        "shape": f"K={k} T={t} D={d} V={v}",
        "naive_bytes": _nbytes(*args) + k * t * 4 + 2 * k * t * v * 4,
    }


def cases(fast: bool = True) -> list[dict]:
    if fast:
        return [
            _case_logit_delta(12214, 50),
            _case_batched_logit_delta(8, 256, 50),
            _case_batched_logit_delta(8, 256, 50, precision="bf16"),
            _case_ar1_delta(8, 512),
            _case_ar1_delta(8, 512, precision="bf16"),
            _case_fused_ce(256, 512, 32_000),
            _case_fused_ce(256, 512, 32_000, precision="fp32"),
            _case_batched_fused_ce(4, 128, 512, 32_000),
            _case_pgibbs_sweep(4, 64, 16, 25),
        ]
    return [
        _case_logit_delta(100_000, 50),
        _case_batched_logit_delta(32, 1024, 50),
        _case_batched_logit_delta(32, 1024, 50, precision="bf16"),
        _case_ar1_delta(32, 2048),
        _case_ar1_delta(32, 2048, precision="bf16"),
        _case_fused_ce(512, 1024, 152_064),
        _case_fused_ce(512, 1024, 152_064, precision="fp32"),
        _case_batched_fused_ce(8, 256, 1024, 152_064),
        _case_pgibbs_sweep(8, 200, 50, 50),
    ]


def measure(case: dict) -> dict:
    kw = case.get("kw", {"mode": "auto"})
    path = case.get("path") or ("pallas" if ops.use_kernel("auto") else "ref")
    fn = jax.jit(lambda *a: case["fn"](*a, **kw))
    sec = _time(fn, *case["args"])
    flops, bmin = case["flops"], case["bytes_min"]
    tpu_compute_s = flops / PEAK_FLOPS
    tpu_memory_s = bmin / HBM_BW
    rec = {
        "kind": "roofline",
        "name": case["name"],
        "path": path,
        "backend": jax.default_backend(),
        "shape": case["shape"],
        "precision": case.get("precision", "fp32"),
        "us_per_call": sec * 1e6,
        "flops": flops,
        "bytes_min": bmin,
        "intensity_flops_per_byte": flops / bmin,
        "gflops": flops / sec / 1e9,
        "gbs": bmin / sec / 1e9,
        "tpu_bound": "compute" if tpu_compute_s >= tpu_memory_s else "memory",
        "tpu_ideal_us": max(tpu_compute_s, tpu_memory_s) * 1e6,
    }
    if "naive_bytes" in case:
        rec["traffic_ratio_naive_over_fused"] = case["naive_bytes"] / bmin
    return rec


def main(fast: bool = True):
    records = [measure(c) for c in cases(fast)]
    payload = {"bench": "roofline", "fast": fast,
               "backend": jax.default_backend(), "records": records}
    path = bench_json_path("roofline")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    rows = [(
        f"roofline_{r['name']}",
        r["us_per_call"],
        f"path={r['path']}_ai={r['intensity_flops_per_byte']:.1f}"
        f"_gflops={r['gflops']:.1f}_tpu_bound={r['tpu_bound']}",
    ) for r in records]
    return rows, records


if __name__ == "__main__":
    for name, us, derived in main()[0]:
        print(f"{name},{us:.1f},{derived}")
