"""Driver `kv_pull` on the CPU at its rehearsal sizes: its control (a
pipeline that does not deliver a record must read `correct` false), the
end-of-window compare of both pools with the reference, the reference's
two forms against each other, and the readers on made-up evidence.
Nothing here is a measurement."""

import types

import numpy as np
import pytest

from benchmark import reference_kv
from benchmark.manifest import Manifest
from benchmark.spans import Spans
from test_rehearsal import ROOT, _rehearse, tiny  # noqa: F401  (fixture)

CELL = "kv_disagg.layerwise_d4"
READERS = ("kv_publish_us", "kv_d2h_rate", "kv_records_per_rpc",
           "kv_registry_us", "kv_fetch_us", "kv_record_wire_us",
           "kv_land_copy_share", "kv_h2d_rate", "kv_page_roofline")
FAULTS = ("record_never_written", "record_of_another_call", "layer_dropped")


class FaultyKvPipeline:
    """A node pipeline over a transport that, from its `after`-th submit
    on (when every landing buffer has been used before), fails one
    record of each block's fetches, the `layer`-th of the submit: its
    bytes stay what the buffer held before, or are those of the record
    that completed before it, or its completion comes back an error."""

    def __init__(self, real, fault: str, after: int, layer: int = 17):
        self._real, self._fault = real, fault
        self._after, self._layer = after, layer
        self._submits = 0
        self._marked: dict = {}      # token -> (buffer, what it held)
        self._bufs: dict = {}
        self._previous = None

    def submit(self, method, requests, resp_bufs=None, **kw):
        self._submits += 1
        before = resp_bufs[self._layer].copy()
        tokens = self._real.submit(method, requests, resp_bufs=resp_bufs,
                                   **kw)
        self._bufs.update(zip(tokens, resp_bufs))
        if self._submits > self._after:
            self._marked[tokens[self._layer]] = before
        return tokens

    def poll(self, **kw):
        from brpc_tpu.rpc.batch import Completion

        done = self._real.poll(**kw)
        for i, c in enumerate(done):
            buf = self._bufs.pop(c.token)
            before = self._marked.pop(c.token, None)
            if before is not None:
                if self._fault == "record_never_written":
                    buf[...] = before
                elif self._fault == "record_of_another_call":
                    buf[...] = self._previous
                elif self._fault == "layer_dropped":
                    done[i] = Completion(c.token, 5, "record dropped", 0,
                                         False, None)
            self._previous = buf.copy()
        return done

    def close(self):
        self._real.close()


def faulty_pipelines(monkeypatch, fault: str, after: int) -> None:
    from brpc_tpu.rpc import Channel

    real = Channel.pipeline
    monkeypatch.setattr(
        Channel, "pipeline",
        lambda self: FaultyKvPipeline(real(self), fault, after))


@pytest.mark.parametrize("fault", FAULTS)
def test_a_record_the_pipeline_did_not_deliver_fails_the_run(
        tiny, monkeypatch, fault):  # noqa: F811
    """The cell's control: the guarantee broken is that a block handed
    over is byte-exact and whole.  Only because every word of a page
    differs from the same word of every earlier page does one stale or
    misdelivered record of 61 fail the compare."""
    after = 10 + int(tiny.cell(CELL).traffic["warm_calls"])
    faulty_pipelines(monkeypatch, fault, after)
    result, notes = _rehearse(tiny, CELL)
    driver = next(n for n in notes if n["note"] == "driver")
    assert result["attempted"] > 10
    assert result["correct"] is False
    assert result["failed"] > 0
    assert result["compared"]["failed_calls"] == {
        "value": result["failed"], "limit": 0}
    if fault == "layer_dropped":
        # The block is not handed over: nothing wrong is written, and
        # the reference, told so, expects the pools as they are.
        assert driver["blocks_mismatched_on_device"] == 0
        assert driver["pool_slots_differing_from_reference"] == 0
        assert result["failed"] <= result["attempted"]
    else:
        assert driver["blocks_mismatched_on_device"] > 0


def test_the_sound_run_hands_every_block_over_and_matches_the_reference(
        tiny):  # noqa: F811
    result, notes = _rehearse(tiny, CELL, seed=2**31 + 11)
    driver = next(n for n in notes if n["note"] == "driver")
    assert result["correct"] is True and result["failed"] == 0
    assert driver["transport"] == driver["transport_expected"] == "shm_ring"
    assert driver["whole_pools_compared"] is True
    assert driver["blocks_produced"] >= result["attempted"]
    assert driver["records_per_block"] == 61
    counted = next(n for n in notes if n["note"] == "counters")
    # Three batch registry RPCs and one multi-record fetch a block, each
    # carrying the block's 61 records; the per-record counters count on.
    assert counted["kv_reg_many_records"] == 61 * counted["kv_reg_many_total"]
    assert counted["kv_fetch_many_records"] == (
        61 * counted["kv_fetch_many_total"])
    assert counted["kv_reg_many_total"] == pytest.approx(
        3 * counted["kv_fetch_many_total"], abs=3)
    assert counted["kv_fetch_total"] == counted["kv_fetch_many_records"]
    other, other_notes = _rehearse(tiny, CELL, seed=8)
    assert next(n for n in other_notes if n["note"] == "driver")[
        "seed_checksum"] != driver["seed_checksum"]


def test_two_blocks_that_swap_slots_fail_the_end_of_window_checksums(
        tiny, monkeypatch):  # noqa: F811
    """Every block's bytes are right and land in the decode pool, but
    from the 20th write on each pair of blocks lands in each other's
    slots: what the pools hold at the end differs from the reference's
    in the slots of the last pair."""
    from brpc_tpu.models import kv_pool

    real = kv_pool.write_page
    writes = 0
    held = None

    def swapping(pool, slot, page):
        nonlocal writes, held
        writes += 1
        if writes < 20:
            return real(pool, slot, page)
        if held is None:
            held = (slot, page)
            return pool
        (first_slot, first_page), held = held, None
        return real(real(pool, first_slot, page), slot, first_page)

    monkeypatch.setattr(kv_pool, "write_page", swapping)
    result, notes = _rehearse(tiny, CELL)
    driver = next(n for n in notes if n["note"] == "driver")
    assert writes > 40
    assert driver["pool_slots_differing_from_reference"] >= 1
    assert result["correct"] is False and result["failed"] > 0


def test_the_reference_followed_in_checksums_is_the_reference_held_whole():
    import jax.numpy as jnp

    rng = np.random.default_rng(27)
    shape = (5, 3, 8, 6)
    prefill = jnp.asarray(rng.integers(0, 1 << 16, shape, dtype=np.uint16))
    decode = jnp.asarray(rng.integers(0, 1 << 16, shape, dtype=np.uint16))
    first = prefill[0]
    sequence = [(3, 1, True), (0, 4, True), (3, 2, False), (2, 1, True),
                (4, 4, True), (0, 0, True)]
    pools = reference_kv.kv_disagg_reference(prefill, decode, first,
                                             sequence)

    def sums(pool):
        return [int(reference_kv.page_checksum(page)) for page in pool]

    words = int(np.prod(shape[1:])) // 2
    followed = reference_kv.kv_disagg_reference_checksums(
        sums(prefill), sums(decode), int(reference_kv.page_checksum(first)),
        words, sequence)
    assert followed == (sums(pools[0]), sums(pools[1]))
    # Block 3 was not handed over: its decode slot is as it was.
    assert np.array_equal(pools[1][2], decode[2])
    # A fresh page differs from the one before in every 32-bit word, and
    # two pages apart too.
    second = reference_kv.next_page(first)
    third = reference_kv.next_page(second)
    for a, b in ((first, second), (second, third), (first, third)):
        assert bool(jnp.all(reference_kv.page_words(a)
                            != reference_kv.page_words(b)))
    assert second.shape == first.shape and second.dtype == first.dtype


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_reads_nothing(name):
    reader = Manifest(ROOT).reader(name)
    assert reader.DRIVERS == ("kv_pull",)
    empty = types.SimpleNamespace(
        counters={}, spans=Spans(), trace=None, t_open=0.0, t_close=1.0,
        call_s=[], bytes_per_call=8994816, device_kind="TPU v5 lite")
    assert reader.read(empty) is None


def test_the_readers_divide_what_the_window_counted():
    spans = Spans()
    for block in range(4):
        at = 0.1 * block
        spans.add("d2h", at, at + 0.001)
        spans.add("d2h_wait", at + 0.001, at + 0.002)
        spans.add("publish", at + 0.002, at + 0.006)
        spans.add("register", at + 0.005, at + 0.006)
        spans.add("fetch", at + 0.006, at + 0.012)
        spans.add("lookup", at + 0.006, at + 0.007)
        spans.add("h2d", at + 0.012, at + 0.014)
        spans.add("evict", at + 0.015, at + 0.016)
    ev = types.SimpleNamespace(
        spans=spans, trace=None, t_open=0.0, t_close=1.0, call_s=[0.05] * 4,
        bytes_per_call=9_000_000, device_kind="TPU v5 lite",
        counters={"kv_reg_many_total": 12.0, "kv_reg_many_records": 732.0,
                  "batch_calls_polled": 244.0, "batch_wire_us": 488000.0,
                  "batch_resp_bytes": 36e6, "batch_land_copy_bytes": 27e6})

    def read(name):
        return Manifest(ROOT).reader(name).read(ev)

    assert read("kv_publish_us") == pytest.approx(5000.0)
    assert read("kv_d2h_rate") == pytest.approx(4.5)
    assert read("kv_records_per_rpc") == 61.0
    assert read("kv_registry_us") == pytest.approx(3000.0)
    assert read("kv_fetch_us") == pytest.approx(5000.0)
    assert read("kv_record_wire_us") == 2000.0
    assert read("kv_land_copy_share") == 75.0
    assert read("kv_h2d_rate") == pytest.approx(4.5)
    roofline = Manifest(ROOT).reader("kv_page_roofline")
    assert roofline.page_hbm_bytes(9_000_000, 3) == 27_000_000
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_bm_kv_produce(1)", 100, 60000],
                ["jit_kv_read_page(2)", 70000, 40000],
                ["jit_kv_write_page(3)", 120000, 40000],
                ["jit_bm_kv_verify(4)", 170000, 50000]]},
            {"name": "XLA Ops", "events": [["%fusion.1 = x", 100, 60000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bm:produce", 0, 1000], ["bm:verify", 200000, 100000]]}]}]}
    ev.trace = trace
    # 7 pages of 9 MB in 140 us: 450 GB/s of the 819.
    assert read("kv_page_roofline") == pytest.approx(
        100 * 63e6 / 140e-6 / 819e9)
