"""Test config: force JAX onto a virtual 8-device CPU mesh (JAX_PLATFORMS
and XLA_FLAGS must be set before the CPU backend initializes).

Tests are CI on the CPU; what stands for the system runs on the chip
(`chip_smoke.py`).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

try:
    import jax  # noqa: F401
except ImportError:
    # big-endian CI tier (qemu-s390x): no jax wheels exist there — only the
    # jax-free suites (layout parity, binfmt, model, asm bytecode) run
    jax = None
else:
    assert jax.devices()[0].platform == "cpu"

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 (ROADMAP.md) runs `-m 'not slow'`; the slow tier holds the
    # live-kernel e2e suites and the 8-device-mesh compile-heavy suites
    # (tier-1 has no slack under its time limit — ROADMAP.md)
    config.addinivalue_line(
        "markers", "slow: live-kernel / multi-device tests excluded from "
        "the tier-1 run (use `-m slow` or no marker filter to include)")

# The real-kernel suites (test_asm_flowpath, test_bpfman, test_prog_load) gate
# on a mounted bpffs; as root, mount it (and tracefs, for the tracepoint
# probes) up front so those tests actually run instead of silently skipping.
if os.geteuid() == 0:
    import ctypes

    _libc = ctypes.CDLL(None, use_errno=True)
    for _fstype, _target in (("bpf", "/sys/fs/bpf"),
                             ("tracefs", "/sys/kernel/tracing")):
        if os.path.isdir(_target) and not os.path.ismount(_target):
            _libc.mount(_fstype.encode(), _target.encode(), _fstype.encode(),
                        0, None)


@pytest.fixture(autouse=True)
def _reset_interface_namer():
    """Isolate the process-global interfaceNamer hook: an agent test that
    starts a live InterfaceListener must not leak its registerer's names
    into later tests (e.g. resolving ifindex 1 -> 'lo')."""
    yield
    from netobserv_tpu.model import record

    record.set_interface_namer(record.default_namer)
