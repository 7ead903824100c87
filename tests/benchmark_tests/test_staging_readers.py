"""The three readers of the stager's counters (`staged_share`,
`call_stage_us`, `stage_d2h_rate`): on made-up counters, in the CPU
rehearsal (every array is host-visible there, so nothing is staged), and
in a rehearsal in which dlpack refuses every JAX array as libtpu does, so
that the driver runs the stager as it does on the chip.  Nothing here is
a measurement."""

import json
import types

import jax
import numpy as np
import pytest

from benchmark import peaks
from benchmark.manifest import Manifest
from test_rehearsal import ROOT, _rehearse, tiny  # noqa: F401  (fixture)

NEW = ("staged_share", "call_stage_us", "stage_d2h_rate")
FIVE = ("call_stage_us", "call_queue_us", "call_wire_us", "call_land_us",
        "call_ready_us")


def _read(name: str, counters: dict):
    reader = Manifest(ROOT).reader(name)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_nothing(name):
    parent = {"batch_calls_polled": 500.0, "batch_ready_us": 1e8}
    assert _read(name, parent) is None
    assert _read(name, {}) is None


def test_the_readers_divide_what_the_window_counted():
    counted = {"batch_calls_polled": 400.0, "batch_staged_calls": 300.0,
               "batch_stage_us": 8e7, "batch_stage_fetch_us": 2e7,
               "batch_stage_fetch_bytes": 300.0 * (64 << 20)}
    assert _read("staged_share", counted) == 75.0
    assert _read("call_stage_us", counted) == 2e5
    assert _read("stage_d2h_rate", counted) == pytest.approx(1.00663296)
    idle = dict.fromkeys(counted, 0.0) | {"batch_calls_polled": 400.0}
    assert [_read(name, idle) for name in NEW] == [0.0, 0.0, 0.0]


def test_one_sided_share_is_over_the_sends_of_the_window():
    """Both counters close with the send, so calls that were sent in the
    window and polled after it (or the other way round) cannot lift the
    share over 100, as they did while the divisor was the polled calls'
    bytes (100.07 on the chip, PR 25)."""
    body = float(64 << 20)
    sent = {"rma_tx_bytes": 30 * body, "stripe_tx_bytes": 10 * body,
            "batch_resp_bytes": 12 * body, "zero_copy_bytes": 0.0}
    assert _read("one_sided_share", sent) == 75.0
    assert _read("one_sided_share", sent | {"stripe_tx_bytes": 0.0}) == 100.0
    assert _read("one_sided_share", sent | {"rma_tx_bytes": 0.0}) == 0.0
    # Bodies under the stripe threshold: none moves one-sided.
    small = {"rma_tx_bytes": 0.0, "stripe_tx_bytes": 0.0,
             "batch_resp_bytes": 4096.0}
    assert _read("one_sided_share", small) == 0.0
    assert _read("one_sided_share", small | {"batch_resp_bytes": 0.0}) is None
    assert _read("one_sided_share", {}) is None


def test_the_manifest_lists_them_for_the_served_cells_only():
    """Asked of the manifest, not read off a cell's name: a cell whose
    driver the readers do not read lists none of the three; a cell they
    read lists the two that exist at every width, and the fetch stream's
    rate from the width at which the driver's step is `echo_fused`."""
    manifest = Manifest(ROOT)
    for name in manifest.cell_names():
        cell = manifest.cell(name)
        listed = {m["name"] for m in cell.per_layer} & set(NEW)
        if any(cell.driver_name not in manifest.reader(n).DRIVERS
               for n in NEW):
            assert not listed
            continue
        fused_from = manifest.driver(cell.driver_name).FUSED_FROM_BYTES
        if cell.traffic["payload_bytes"] >= fused_from:
            assert listed == set(NEW)
        else:
            assert listed == {"staged_share", "call_stage_us"}


def _v5e_peaks_for_the_cpu(tmp_path, monkeypatch):
    # As in the rehearsal's traced test: the recorded trace is a v5e's.
    table = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    table["cpu"] = table["TPU v5 lite"]
    (tmp_path / "peaks.json").write_text(json.dumps(table))
    monkeypatch.setattr(peaks, "_TABLE", tmp_path / "peaks.json")


def test_on_the_cpu_nothing_is_staged(tiny, tmp_path, monkeypatch):  # noqa: F811
    _v5e_peaks_for_the_cpu(tmp_path, monkeypatch)
    result, _ = _rehearse(tiny, "echo_tcp.tensor64M", trace=True)
    assert result["correct"] is True
    assert [result["metrics"][name]["value"] for name in NEW] == [0, 0, 0]


@pytest.mark.parametrize("cell", ["echo_tcp.small1K", "echo_shm.tensor64M"])
def test_where_dlpack_refuses_the_arrays_every_call_is_staged(
        tiny, tmp_path, monkeypatch, cell):  # noqa: F811
    _v5e_peaks_for_the_cpu(tmp_path, monkeypatch)
    real = np.from_dlpack

    def refuses_jax(x, *args, **kwargs):
        if isinstance(x, jax.Array):
            raise RuntimeError("not a DLPack device (rehearsed)")
        return real(x, *args, **kwargs)

    monkeypatch.setattr(np, "from_dlpack", refuses_jax)
    result, notes = _rehearse(tiny, cell, trace=True)
    assert result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["staged_share"] == 100.0
    assert metrics["call_stage_us"] > 0
    if "stage_d2h_rate" in metrics:
        assert metrics["stage_d2h_rate"] > 0
    # Five phases: from `host_view` starting the transfer to the poll.
    # The `wire` span starts when `pipe.submit` returns, so the spans
    # before it (one `d2h` and a share of one `submit` a call) are the
    # difference, a few per cent of a call here, under 0.1 % on the chip.
    spans = next(n for n in notes if n["note"] == "spans")
    mean_us = {name: spans[name]["total_s"] / spans[name]["n"] * 1e6
               for name in ("wire", "submit", "d2h")}
    five = sum(metrics[name] for name in FIVE)
    assert mean_us["wire"] <= five * 1.02
    assert five <= (mean_us["wire"] + mean_us["submit"]) * 1.05 + \
        8 * mean_us["d2h"]
