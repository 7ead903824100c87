"""The reader of `kv_registry_srv_us` (PR 35): the server's share of one
registry round trip, from the server's own per-method fold, on made-up
counters and through the manifest.  Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from test_rehearsal import ROOT

NAME = "kv_registry_srv_us"
METHODS = ("KvReg.RegisterMany", "KvReg.LookupMany", "KvReg.EvictMany")


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


def _fold(method: str, calls: float, queue: float, handler: float,
          send: float) -> dict:
    base = f"rpc_server_{method}_"
    return {base + "calls": calls, base + "queue_us": queue,
            base + "handler_us": handler, base + "send_us": send}


@pytest.mark.parametrize("counters", [
    {},
    # The parent: the registry's own totals, and no phase fold.
    {"kv_reg_many_total": 12000.0, "kv_reg_many_records": 12000 * 61.0},
    # Registered, and no registry call in the window.
    {**_fold(METHODS[0], 0.0, 0.0, 0.0, 0.0),
     **_fold(METHODS[1], 0.0, 0.0, 0.0, 0.0)},
    # Only the store's method answered.
    _fold("Kv.Fetch", 4200 * 61.0, 1e6, 1e6, 1e6),
], ids=["no_counter", "the_parent", "no_registry_call", "fetches_only"])
def test_without_a_registry_call_it_reads_nothing(counters):
    assert _read(counters) is None


def test_the_reader_is_the_mean_over_the_three_methods_round_trips():
    # 4,000 blocks: one call of each a block; a register costs the
    # server 120 us, a lookup 60, an evict 90.
    counters = {**_fold(METHODS[0], 4000.0, 4000 * 20.0, 4000 * 95.0,
                        4000 * 5.0),
                **_fold(METHODS[1], 4000.0, 4000 * 20.0, 4000 * 30.0,
                        4000 * 10.0),
                **_fold(METHODS[2], 4000.0, 4000 * 20.0, 4000 * 65.0,
                        4000 * 5.0),
                **_fold("Kv.Fetch", 4000 * 61.0, 9e9, 9e9, 9e9)}
    assert _read(counters) == pytest.approx((120 + 60 + 90) / 3)


def test_a_method_the_window_never_called_weighs_nothing():
    counters = {**_fold(METHODS[0], 10.0, 100.0, 800.0, 100.0),
                **_fold(METHODS[2], 0.0, 0.0, 0.0, 0.0)}
    assert _read(counters) == pytest.approx(100.0)


def test_the_manifest_lists_it_in_the_two_kv_cells():
    manifest = Manifest(ROOT)
    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    entry = by_name[NAME]
    assert entry["workloads"] == ["kv_disagg.layerwise_d4",
                                  "kv_hybrid.handover1k_d2"]
    assert entry["layer"] == by_name["kv_registry_us"]["layer"]
    assert (entry["moves"], entry["better"]) == ("call_p50", "lower")
    assert entry["source"] == "program_counter"
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "us"
    assert {manifest.cell(name).driver_name
            for name in entry["workloads"]} == set(reader.DRIVERS)
