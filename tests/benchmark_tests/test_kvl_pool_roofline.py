"""The reader of `kvl_pool_roofline`: the pool programs' share of the
HBM roofline, on made-up evidence and through the manifest. Nothing here
is a measurement."""

import gzip
import json
import types

import pytest

from benchmark import work_kv_prefix_linear as work
from benchmark.manifest import Manifest
from benchmark.spans import Spans
from test_rehearsal import ROOT

NAME = "kvl_pool_roofline"
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
        "Device kernel", "goodput", "higher", "device_trace")
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "%"
    assert reader.DRIVERS == ("kv_prefix_linear",)


PAGE, STATE = 1032192, 43417600
COUNTED = {"produce_runs": 2, "pages_produced": 8, "state_runs": 2,
           "state_pages": 8, "pages_read": 8, "states_read": 2,
           "pages_written": 2000, "states_written": 2}
NOTES = {"page_bytes": PAGE, "state_bytes": STATE, "traced": COUNTED}


def _trace():
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_bm_kvl_produce(1)", 100, 100000],
                ["jit_bm_kvl_state_produce(2)", 200000, 300000],
                ["jit_kv_read_pages(3)", 600000, 50000],
                ["jit_kv_write_pages(4)", 700000, 6000000],
                ["jit_kv_read_page(5)", 6800000, 200000],
                ["jit_kv_write_page(6)", 7100000, 250000],
                ["jit_bm_kvl_verify(7)", 7400000, 900000]]},
            {"name": "XLA Ops", "events": [["%fusion.1 = x", 100, 60000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bm:produce", 0, 1000], ["bm:verify", 7400000, 1000000]]}]}]}


def test_without_a_trace_or_counts_it_reads_nothing():
    assert _read(notes=NOTES) is None
    assert _read(trace=_trace(), notes={}) is None


def test_it_counts_each_program_by_its_shapes():
    moved = ((2 + 16) * PAGE + (8 * PAGE + 6 * STATE) + 16 * PAGE
             + 4000 * PAGE + 4 * STATE + 4 * STATE)
    assert moved == (work.produce_hbm_bytes(8, 2, PAGE)
                     + work.state_produce_hbm_bytes(8, 2, PAGE, STATE)
                     + work.read_pages_hbm_bytes(8, PAGE)
                     + work.write_pages_hbm_bytes(2000, PAGE)
                     + work.read_state_hbm_bytes(2, STATE)
                     + work.write_state_hbm_bytes(2, STATE))
    seconds = (100000 + 300000 + 50000 + 6000000 + 200000 + 250000) / 1e9
    got = _read(trace=_trace(), notes=NOTES)
    assert got == pytest.approx(100 * moved / seconds / 819e9)


def test_it_reads_the_trace_recorded_on_the_chip_under_100():
    manifest = Manifest(ROOT)
    with gzip.open(manifest.home / "testdata"
                   / "trace_kv_prefix_linear.json.gz", "rt") as f:
        trace = json.load(f)
    from benchmark import trace_reduce

    runs = {pattern: trace_reduce.count_by_name(
        trace, trace_reduce.MODULE_LINE, pattern)
        for pattern in manifest.reader(NAME).MODULES}
    assert all(n > 0 for n in runs.values()), runs
    # At one page a run and the least work a run can be, the share is
    # the least the recorded times allow.
    counted = {"produce_runs": 0, "pages_produced": 0, "state_runs": 0,
               "state_pages": 0, "pages_read": 0, "states_read": 0,
               "pages_written": 0, "states_written": 0}
    patterns = manifest.reader(NAME).MODULES
    counted["produce_runs"] = counted["pages_produced"] = runs[patterns[0]]
    counted["state_runs"] = counted["state_pages"] = runs[patterns[1]]
    counted["pages_read"] = runs[patterns[2]]
    counted["pages_written"] = runs[patterns[3]]
    counted["states_read"] = runs[patterns[4]]
    counted["states_written"] = runs[patterns[5]]
    got = _read(trace=trace, notes=dict(NOTES, traced=counted))
    assert 0 < got < 100
    assert 0 < manifest.reader("device_idle_share").read(
        types.SimpleNamespace(trace=trace)) < 100
