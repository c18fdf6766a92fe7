"""utils/platform.pin_malloc_thresholds: fixed glibc heap thresholds and top
pad, unless the operator's environment already sets malloc's own."""

import ctypes.util

import pytest

from netobserv_tpu.utils import platform

glibc = pytest.mark.skipif(
    "libc.so.6" not in (ctypes.util.find_library("c") or ""),
    reason="glibc only")


@glibc
def test_pins_and_is_idempotent(monkeypatch):
    for var in ("MALLOC_TRIM_THRESHOLD_", "MALLOC_MMAP_THRESHOLD_",
                "MALLOC_TOP_PAD_", "GLIBC_TUNABLES"):
        monkeypatch.delenv(var, raising=False)
    assert platform.pin_malloc_thresholds()
    assert platform.pin_malloc_thresholds()


@pytest.mark.parametrize("var, value", [
    ("MALLOC_TRIM_THRESHOLD_", "1048576"),
    ("MALLOC_MMAP_THRESHOLD_", "131072"),
    ("MALLOC_TOP_PAD_", "0"),
    ("GLIBC_TUNABLES", "glibc.malloc.trim_threshold=1048576"),
])
def test_operator_malloc_settings_win(monkeypatch, var, value):
    monkeypatch.setenv(var, value)
    assert platform.pin_malloc_thresholds() is False


@glibc
def test_unrelated_tunables_still_pin(monkeypatch):
    monkeypatch.delenv("MALLOC_TRIM_THRESHOLD_", raising=False)
    monkeypatch.delenv("MALLOC_MMAP_THRESHOLD_", raising=False)
    monkeypatch.delenv("MALLOC_TOP_PAD_", raising=False)
    monkeypatch.setenv("GLIBC_TUNABLES", "glibc.pthread.rseq=0")
    assert platform.pin_malloc_thresholds()


def test_agent_pins_at_construction(monkeypatch):
    from netobserv_tpu.agent import agent as agent_mod
    from netobserv_tpu.config import load_config
    from netobserv_tpu.datapath.fetcher import FakeFetcher
    from netobserv_tpu.exporter.base import Exporter

    calls = []
    monkeypatch.setattr(platform, "pin_malloc_thresholds",
                        lambda: calls.append(1) or True)

    class NullExporter(Exporter):
        name = "null"

        def export_batch(self, records):
            pass

    cfg = load_config(environ={"EXPORT": "stdout"})
    agent_mod.FlowsAgent(cfg, FakeFetcher(), NullExporter())
    assert calls == [1]
