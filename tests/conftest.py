"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the analog of the reference's
spawn-on-localhost fake cluster, test/legacy_test/test_parallel_dygraph_dataparallel.py:30)
so multi-chip sharding logic is exercised without TPU hardware. These env vars
must be set before jax is imported anywhere in the process.
"""

import os
import sys

# PADDLE_TPU_HW=1: run on the real TPU chip (one pytest process per chip-tool
# command; README "Running"). Default: virtual 8-device CPU mesh.
# Interpret-mode Pallas provably hides Mosaic layout bugs (round-2 finding),
# so kernel tests honor this flag too (see
# tests/test_pallas_kernels.py::_interpret_mode).
_ON_HW = os.environ.get("PADDLE_TPU_HW") == "1"

if not _ON_HW:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Numeric-parity oracle tests need full-precision GEMMs (the TPU bf16-pass
# default is a perf choice, not a correctness one) — same stance as the
# reference's FLAGS_cudnn_deterministic test mode.
import jax  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _flight_file_in_tmp(tmp_path, monkeypatch):
    """The flight recorder's default dump path is the cwd (production: the
    launcher points it at the worker log dir). Tests that legitimately
    crash a trainer (hold timeout, injected faults) must not litter the
    repo root — default every test's post-mortems into its tmp dir."""
    monkeypatch.setenv("PADDLE_FLIGHT_FILE",
                       str(tmp_path / "flight_recorder.json"))


@pytest.fixture
def fault_injector(monkeypatch):
    """Resilience fault harness (tools/fault_inject.py + distributed/faults):
    arm in-process fault points via env, corrupt/truncate checkpoint files.

        def test_x(fault_injector, tmp_path):
            fault_injector.arm("ckpt.before_commit", "exc")   # or kill/sleep
            fault_injector.corrupt(ckpt_dir)                  # flip bytes
            fault_injector.truncate(ckpt_dir, frac=0.3)
    """
    from paddle_tpu.distributed import faults
    from tools import fault_inject as fi

    class _Injector:
        def arm(self, point, action, arg=None, nth=None):
            spec = f"{point}:{action}" + (f":{arg}" if arg is not None else "")
            if nth is not None:
                spec += f"@{nth}"
            prev = os.environ.get("PADDLE_FAULT_INJECT", "")
            faults.reset()  # fresh @n counters even for an identical spec
            monkeypatch.setenv("PADDLE_FAULT_INJECT",
                               f"{prev},{spec}" if prev else spec)

        def disarm(self):
            monkeypatch.delenv("PADDLE_FAULT_INJECT", raising=False)
            faults.reset()

        corrupt = staticmethod(fi.corrupt_file)
        truncate = staticmethod(fi.truncate_file)

    return _Injector()


@pytest.fixture
def pallas_interpret_unless_hw(monkeypatch):
    """Interpret-mode Pallas hides Mosaic layout bugs (round-2 finding); under
    PADDLE_TPU_HW=1 kernels must compile on the real chip, so clear any
    leftover interpret var (a TPU backend refuses it) instead of setting
    it."""
    if _ON_HW:
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; slow marks the fault-injection tests that
    # fork full worker pods and wait out real watchdog deadlines
    config.addinivalue_line(
        "markers", "slow: multi-process fault-injection/recovery tests "
                   "excluded from tier-1 (`-m 'not slow'`)")


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Tier-1 runs under a hard wall-clock budget (ROADMAP 870 s timeout);
    print the session's total wall time so budget creep shows up in CI logs
    as a number, not as a surprise rc=124."""
    import time

    start = getattr(terminalreporter, "_sessionstarttime", None)
    if start is not None:
        terminalreporter.write_sep(
            "-", f"session wall time: {time.time() - start:.1f}s "
                 "(tier-1 budget: 870s)")


def pytest_collection_modifyitems(config, items):
    """PADDLE_TPU_HW=1 runs on the real chip, where the virtual 8-device CPU
    mesh is NOT configured — multi-device tests would all fail on a 1-chip
    host. Only the kernel/hardware-validation subsets are meant for that
    flag; skip the rest instead of failing them."""
    if not _ON_HW:
        return
    n = len(jax.devices())
    if n >= 8:
        return
    hw_safe_files = {
        "test_pallas_kernels.py", "test_masked_flash.py", "test_rnn.py",
        "test_autotune.py", "test_fused_attention.py", "test_amp_conv.py",
        "test_fused_norm_rope.py", "test_decode_attention.py",
        "test_serving_paged.py", "test_serving_quant.py",
        "test_kernel_partition.py",  # skips itself below 4 devices
    }
    # files that are mostly multi-device: only their kernel tests
    hw_safe_nodes = {
        "test_moe.py": ("TestGroupedGemm", "test_kernel_path_parity"),
    }
    skip = pytest.mark.skip(
        reason=f"PADDLE_TPU_HW=1 with {n} device(s): needs the 8-device "
               "virtual CPU mesh (run without the flag)")
    for item in items:
        name = item.fspath.basename
        if name in hw_safe_files:
            continue
        if any(part in item.nodeid for part in hw_safe_nodes.get(name, ())):
            continue
        item.add_marker(skip)
