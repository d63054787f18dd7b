"""Fail-loud guards on the way to the chip, checked on the CPU with the
backend query stubbed: where the device cannot be what the code assumes,
the code refuses instead of silently running on the CPU. Plus the
compile-cache placement rule and the chip smoke script's refusal."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache
from repro.kernels import autotune

_REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def tpu_backend(monkeypatch):
    """Make ``jax.default_backend()`` report a TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_replica_process_refuses_on_tpu_parent(tpu_backend):
    from repro.fleet.replica import ReplicaProcess

    with pytest.raises(RuntimeError, match="silently run on the CPU"):
        ReplicaProcess("r0", "bayeslr", {"smoke": True})


def test_serve_devices_flag_refused_on_tpu(tpu_backend, monkeypatch, capsys):
    from repro.launch import serve

    monkeypatch.setenv("XLA_FLAGS", "")  # main() writes it; restored after
    with pytest.raises(SystemExit) as exc:
        serve.main(["--fleet", "--devices", "4", "--workload", "bayeslr"])
    assert exc.value.code == 2
    assert "no effect on a TPU" in capsys.readouterr().err


def test_fleet_bench_refuses_on_tpu(tpu_backend):
    sys.path.insert(0, str(_REPO))  # benchmarks/ is a repo-root package
    try:
        from benchmarks import fleet_bench
    finally:
        sys.path.remove(str(_REPO))
    with pytest.raises(SystemExit, match="refuses to run on a TPU host"):
        fleet_bench.main()


def test_autotune_raises_when_no_candidate_compiles(monkeypatch, tmp_path):
    def refused(*args, **kw):
        raise ValueError("block shape (1, 8) refused by the compiler")

    monkeypatch.setenv(autotune.ENV_VAR, "1")
    monkeypatch.setenv(autotune.DIR_ENV_VAR, str(tmp_path))
    monkeypatch.setattr(autotune, "_memory_cache", {})
    monkeypatch.setattr(autotune, "_loaded_backends", set())
    monkeypatch.setattr(autotune, "_kernel_fn", lambda family: refused)
    with pytest.raises(RuntimeError, match=r"no gaussian_ar1 candidate.*\(1, 8\) refused"):
        autotune.tiles_for("gaussian_ar1", (3, 20))
    assert not (tmp_path / f"{jax.default_backend()}.json").exists()


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, config_updates):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert config_updates == []  # JAX reads the variable itself


def test_compile_cache_default_is_fixed_checkout_path(monkeypatch, config_updates):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = str(_REPO / ".jax_cache")
    assert compile_cache.enable() == want
    assert compile_cache.enable() == want
    assert config_updates == [("jax_compilation_cache_dir", want)] * 2


def test_chip_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(_REPO / "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
