"""The reader of `kvl_one_sided_share`: the share of the restored bytes
that moved one-sided, on made-up evidence and through the manifest.
Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from benchmark.spans import Spans
from test_rehearsal import ROOT

NAME = "kvl_one_sided_share"
CELL = "kv_prefix_linear.agent4_128k_replay"


def _read(**fields):
    ev = dict(counters={}, spans=Spans(), trace=None, t_open=0.0,
              t_close=10.0, call_s=[], bytes_per_call=0.0,
              device_kind="TPU v5 lite", notes={})
    ev.update(fields)
    return Manifest(ROOT).reader(NAME).read(types.SimpleNamespace(**ev))


def test_the_manifest_lists_it_in_the_hybrid_prefix_cell():
    manifest = Manifest(ROOT)
    entry = next(m for m in manifest.doc["per_layer"] if m["name"] == NAME)
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["moves"], entry["better"],
            entry["source"]) == (
        "Transport", "goodput", "higher", "program_counter")
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "%"
    assert reader.DRIVERS == ("kv_prefix_linear",)


@pytest.mark.parametrize("fields", [
    {},
    {"counters": {"rma_tx_bytes": 1e9}},
    {"bytes_per_call": 1.1e9, "call_s": [0.5] * 3},
], ids=["nothing", "no_turn", "no_counter"])
def test_without_restored_bytes_and_the_counter_it_reads_nothing(fields):
    assert _read(**fields) is None


def test_it_reads_the_states_share_of_the_restored_bytes():
    # Three turns of 1,050 pages and a state: the states went one-sided,
    # the pages in frames (counted in neither counter).
    turn = 1050 * 1032192 + 43417600
    got = _read(bytes_per_call=turn, call_s=[0.5] * 3,
                counters={"rma_tx_bytes": 3 * 43417600.0,
                          "stripe_tx_bytes": 0.0})
    assert got == pytest.approx(100 * 43417600 / turn)
    assert 3.5 < got < 4.5
