"""The five readers under ``hbm_peak_gb`` (``harness/memory_gauges.py`` and the
``layer_metrics/`` files over it) on gauges set by hand: nothing to read from a
program without them, the gauge's bytes / 1e9 with them."""

import os

import pytest

from harness import spec
from paddle_tpu.fluid import telemetry

BENCH = spec.load_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
READERS = ("step_temp_gb", "step_state_gb", "feed_staged_gb",
           "hbm_in_use_peak_gb", "hbm_reserved_peak_gb")
GAUGES = ("step_memory_bytes", "step_resident_bytes", "feed_staged_bytes",
          "device_memory_bytes")


def read(name):
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "layer_metrics", name + ".py")).read({})


@pytest.fixture(autouse=True)
def empty_registry(monkeypatch):
    """No gauge holds a value, and sampling the devices sets none (the CPU
    gives no statistics; a test sets the books by hand)."""
    for name in GAUGES:
        telemetry.registry().gauge(name).reset()
    yield
    for name in GAUGES:
        telemetry.registry().gauge(name).reset()


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_from_an_empty_registry(name):
    assert read(name) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_from_a_program_without_the_gauges(name, monkeypatch):
    """The parent's side of the driver's pair: no such gauge, no sampler."""
    monkeypatch.setattr(telemetry.registry(), "get", lambda name: None)
    monkeypatch.delattr(telemetry, "sample_device_memory")
    assert read(name) is None


def test_the_step_is_the_signature_with_the_most_temporaries():
    memory = telemetry.registry().gauge("step_memory_bytes")
    resident = telemetry.registry().gauge("step_resident_bytes")
    for sig, temp in (("aaa:k1", 3_000_000), ("bbb:k1", 9_866_375_680)):
        memory.set(temp, sig=sig, kind="temp")
        memory.set(17, sig=sig, kind="alias")
    for kind, nbytes in (("parameter", 2_000_000_000),
                         ("optimizer_state", 4_000_000_000),
                         ("other_state", 40), ("feed", 123)):
        resident.set(nbytes, sig="bbb:k1", kind=kind)
        resident.set(1, sig="aaa:k1", kind=kind)
    assert read("step_temp_gb") == 9_866_375_680 / 1e9
    assert read("step_state_gb") == 6_000_000_040 / 1e9      # no feed in it


def test_the_staged_feeds_peak():
    staged = telemetry.registry().gauge("feed_staged_bytes")
    staged.set(154_140_672, stat="now")
    assert read("feed_staged_gb") is None
    staged.set(616_562_688, stat="peak")
    assert read("feed_staged_gb") == 616_562_688 / 1e9


def test_both_device_readings_come_from_the_fullest_device():
    books = telemetry.registry().gauge("device_memory_bytes")
    # device 1 holds the largest sum; device 0 the largest single reading,
    # device 2 the largest reserve
    for device, in_use, reserved in ((0, 9_000, 100), (1, 8_000, 4_000),
                                     (2, 1_000, 5_000)):
        books.set(in_use, device=device, stat="peak_bytes_in_use")
        books.set(reserved, device=device, stat="peak_bytes_reserved")
        books.set(1, device=device, stat="bytes_in_use")
    in_use, reserved = read("hbm_in_use_peak_gb"), read("hbm_reserved_peak_gb")
    assert (in_use, reserved) == (8_000 / 1e9, 4_000 / 1e9)
    assert in_use + reserved == (8_000 + 4_000) / 1e9


def test_the_entries_move_hbm_peak_gb_in_every_cell():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    end_to_end = {m["name"] for m in BENCH["end_to_end"]}
    for name in READERS:
        entry = entries[name]
        assert os.path.exists(os.path.join(
            spec.BENCH_DIR, "layer_metrics", name + ".py"))
        assert entry["moves"] == "hbm_peak_gb" and \
            entry["moves"] in end_to_end
        assert "workloads" not in entry
        assert (entry["unit"], entry["better"], entry["source"]) == \
            ("GB", "lower", "program_counter")
