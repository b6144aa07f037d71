"""Bring-up guards that need no chip (ISSUE 21): the places that could
hide a missing or refusing chip fail or report instead — native kernel
construction under the installed JAX, the Pallas mode on an accelerator,
VMEM refusals vs HBM OOMs, the platform gate, where the compile cache
goes, a budget that is never guessed on an accelerator, and the two
entry scripts' exit codes."""

import os
import subprocess
import sys

import pytest

import jax

from spark_rapids_tpu import device_caps as DC
from spark_rapids_tpu import device_manager
from spark_rapids_tpu import kernels as KR
from spark_rapids_tpu import memory as MEM
from spark_rapids_tpu import retry as R
from spark_rapids_tpu.conf import TpuConf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tiled_groupby_kernel_constructs_natively():
    """interpret=False takes the Mosaic compiler-params path, which
    interpret-mode tests never reach (it named an API the installed
    JAX no longer has)."""
    from spark_rapids_tpu.kernels import groupby_hash as KG
    assert callable(KG._build_kernel_tiled(512, 1, 3, 1, 1, 128, False))


@pytest.fixture
def fresh_pallas_mode():
    DC.pallas_mode.cache_clear()
    yield
    DC.pallas_mode.cache_clear()


def test_pallas_mode_never_interprets_on_an_accelerator(
        monkeypatch, fresh_pallas_mode):
    """Backend faked to tpu: the native probe fails here (XLA:CPU only
    interprets), and the answer is None — never "interpret"."""
    assert DC.pallas_mode() == "interpret"
    DC.pallas_mode.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert DC.pallas_mode() is None


def test_vmem_exhaustion_is_a_kernel_refusal_not_an_oom():
    vmem = RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space vmem. Used 143.05M of 16.00M vmem.")
    hbm = RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "17179869184 bytes in memory space hbm.")
    assert R.is_vmem_refusal(vmem) and not R.is_oom_error(vmem)
    assert KR.is_oracle_fallback_error(vmem)
    assert R.is_oom_error(hbm) and not R.is_vmem_refusal(hbm)
    assert not KR.is_oracle_fallback_error(hbm)
    assert not KR.is_oracle_fallback_error(R.TpuRetryOOM("injected"))


def test_native_gate_follows_the_refusal_table(monkeypatch):
    conf = TpuConf({})
    monkeypatch.setattr(KR, "NATIVE_REFUSED", {"murmur3": "refused"})
    # interpret mode (this backend) runs every kernel, listed or not
    assert KR.kernel_enabled(conf, "murmur3")
    monkeypatch.setattr(DC, "pallas_mode", lambda: "native")
    assert not KR.kernel_enabled(conf, "murmur3")
    assert KR.kernel_enabled(conf, "joinProbe")
    monkeypatch.setattr(DC, "pallas_mode", lambda: None)
    assert not KR.kernel_enabled(conf, "joinProbe")


def test_every_refused_kernel_is_a_registered_kernel():
    assert set(KR.NATIVE_REFUSED) <= set(KR.KERNELS)
    assert all(KR.NATIVE_REFUSED.values())


def test_poison_keeps_the_reason():
    KR.clear_poison()
    try:
        KR.poison("murmur3", ("k",), ValueError("first line\nsecond"))
        assert KR.is_poisoned("murmur3", ("k",))
        assert KR.poisoned() == {
            ("murmur3", ("k",)): "ValueError: first line"}
    finally:
        KR.clear_poison()
    assert not KR.poisoned()


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
