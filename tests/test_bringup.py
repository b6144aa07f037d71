"""Bring-up guards that need no chip (ISSUE 21): the places that could
hide a missing or refusing chip fail or report instead — VMEM refusals
vs HBM OOMs, the platform gate, where the compile cache goes, a budget
that is never guessed on an accelerator, and the two entry scripts'
exit codes."""

import os
import subprocess
import sys

import pytest

import jax

from spark_rapids_tpu import device_manager
from spark_rapids_tpu import memory as MEM
from spark_rapids_tpu import retry as R

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_vmem_exhaustion_is_a_kernel_refusal_not_an_oom():
    vmem = RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space vmem. Used 143.05M of 16.00M vmem.")
    hbm = RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "17179869184 bytes in memory space hbm.")
    assert R.is_vmem_refusal(vmem) and not R.is_oom_error(vmem)
    assert R.is_oom_error(hbm) and not R.is_vmem_refusal(hbm)
    assert R.is_oom_error(R.TpuRetryOOM("injected"))


def test_no_guessed_budget_on_an_accelerator(monkeypatch):
    assert MEM._default_budget() == MEM._CPU_EMULATION_BUDGET
    monkeypatch.setattr(device_manager, "device_memory_bytes",
                        lambda: 16 << 30)
    assert MEM._default_budget() == int((16 << 30) * 0.8)
    monkeypatch.setattr(device_manager, "device_memory_bytes",
                        lambda: None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="bytes_limit"):
        MEM._default_budget()


_CACHE_PROBE = (
    "import jax; from spark_rapids_tpu import device_manager; "
    "device_manager.initialize(); "
    "print(jax.config.jax_compilation_cache_dir)")


def _cache_dir_of_fresh_process(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_ENABLE_COMPILATION_CACHE")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd="/")
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; the program adds no
    directory of its own and no subdirectory."""
    want = str(tmp_path / "placed-from-outside")
    assert _cache_dir_of_fresh_process(want) == want
    assert not os.path.exists(want)  # nothing compiled, nothing made


def test_compile_cache_default_is_fixed_under_the_checkout():
    assert _cache_dir_of_fresh_process(None) == os.path.join(
        REPO, ".xla_cache", "cpu")


def test_compile_cache_stays_off_under_pytest():
    assert not jax.config.jax_enable_compilation_cache
    device_manager.initialize()
    assert jax.config.jax_compilation_cache_dir is None


def test_bench_leg_failure_is_recorded_not_skipped():
    import bench
    failed = []
    assert bench.run_leg(failed, "fine leg", lambda x: {"v": x}, 3) == {
        "v": 3}
    assert failed == []
    out = bench.run_leg(failed, "broken leg", lambda: 1 // 0)
    assert out["failed"] is True and "ZeroDivisionError" in out["reason"]
    assert "skipped" not in out
    assert failed == ["broken leg"]


def test_chip_smoke_refuses_to_run_without_a_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path))
    assert out.returncode != 0
    assert out.stdout == ""  # no result line, no leg output
    assert "not 'tpu'" in out.stderr
    assert not os.path.exists(
        os.path.join(REPO, ".bench-data", "chip_smoke"))
