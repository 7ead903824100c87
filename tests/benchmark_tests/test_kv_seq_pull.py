"""Driver `kv_seq_pull` on the CPU at its rehearsal sizes (the snapshot
record at its real 2,170,880 B, so the one-sided window and the rails'
landing copy run): its control (a hand-over with a stale snapshot, a
page record left out or a snapshot cut at the stripe threshold must read
`correct` false), the end-of-window compare of all four pools with the
reference, the reference's two forms against each other, the mix against
the configuration's widths, and the readers on made-up evidence.
Nothing here is a measurement.

On the chip the control is `control_on_the_chip` below, run as
`python3 tests/benchmark_tests/test_kv_seq_pull.py <fault> <seed>
<seconds>` from the root of a checkout: the same faults under
`run.run_cell` at the timed sizes."""

import json
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(pathlib.Path(__file__).parent)]

from benchmark import reference_kv, reference_kv_hybrid  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.spans import Spans  # noqa: E402
from test_rehearsal import _rehearse, tiny  # noqa: E402,F401  (fixture)

CELL = "kv_hybrid.handover1k_d2"
READERS = ("kvh_publish_us", "kvh_h2d_rate", "kvh_registry_us",
           "kvh_fetch_us", "kvh_one_sided_share", "kvh_land_copy_share",
           "kvh_pool_roofline")
FAULTS = ("stale_snapshot", "page_record_left_out",
          "snapshot_cut_at_the_threshold")
THRESHOLD = 2 << 20


class FaultyKvSeqPipeline:
    """A node pipeline over a transport that, from its `after`-th submit
    on (when every landing area has held an earlier sequence), fails one
    record of each hand-over's round: the second snapshot record keeps
    the bytes its place held before (an earlier sequence's snapshot of
    the same layer: stale), or the second page record's completion comes
    back an error, or the second snapshot record's completion says
    2,097,152 bytes and the rest of its place is as it was.  Which
    records of a round are snapshots it tells by their places' size."""

    def __init__(self, real, fault: str, after: int):
        self._real, self._fault, self._after = real, fault, after
        self._submits = 0
        self._marked: dict = {}      # token -> (its place, what it held)

    def submit(self, method, requests, resp_bufs=None, **kw):
        self._submits += 1
        largest = max(buf.nbytes for buf in resp_bufs)
        snapshots = [i for i, buf in enumerate(resp_bufs)
                     if buf.nbytes == largest]
        at = 1 if self._fault == "page_record_left_out" else snapshots[1]
        assert resp_bufs[at].nbytes == (
            largest if at in snapshots else min(
                buf.nbytes for buf in resp_bufs))
        before = resp_bufs[at].copy()
        tokens = self._real.submit(method, requests, resp_bufs=resp_bufs,
                                   **kw)
        if self._submits > self._after:
            self._marked[tokens[at]] = (resp_bufs[at], before)
        return tokens

    def poll(self, **kw):
        from brpc_tpu.rpc.batch import Completion

        done = self._real.poll(**kw)
        for i, c in enumerate(done):
            place, before = self._marked.pop(c.token, (None, None))
            if place is None:
                continue
            if self._fault == "stale_snapshot":
                place[...] = before
            elif self._fault == "page_record_left_out":
                done[i] = Completion(c.token, 5, "record left out", 0,
                                     False, None)
            else:
                place[THRESHOLD:] = before[THRESHOLD:]
                done[i] = Completion(c.token, 0, "", THRESHOLD,
                                     c.in_caller_buffer, c.data)
        return done

    def close(self):
        self._real.close()


def faulty_pipelines(monkeypatch, fault: str, after: int) -> None:
    from brpc_tpu.rpc import Channel

    real = Channel.pipeline
    monkeypatch.setattr(
        Channel, "pipeline",
        lambda self: FaultyKvSeqPipeline(real(self), fault, after))


@pytest.mark.parametrize("fault", FAULTS)
def test_a_hand_over_with_a_record_wrong_fails_the_run(
        tiny, monkeypatch, fault):  # noqa: F811
    """The cell's control: the guarantee broken is that a sequence handed
    over is byte-exact and whole, pages and states of one boundary.  Only
    because every word of a sequence's states differs from the same word
    of every earlier sequence's does a stale snapshot, which nothing else
    tells from a fresh one, fail the compare."""
    after = 6 + int(tiny.cell(CELL).traffic["warm_calls"])
    faulty_pipelines(monkeypatch, fault, after)
    result, notes = _rehearse(tiny, CELL)
    driver = next(n for n in notes if n["note"] == "driver")
    counted = next(n for n in notes if n["note"] == "counters")
    assert result["attempted"] > 6
    assert result["correct"] is False
    assert result["failed"] > 0
    assert result["compared"]["failed_calls"] == {
        "value": result["failed"], "limit": 0}
    if fault == "stale_snapshot":
        assert driver["sequences_mismatched_on_device"] > 0
        assert driver["sequences_refused"] == 0
    else:
        # The hand-over is refused whole: nothing wrong is written, and
        # the reference, told so, expects the pools as they are.
        assert driver["sequences_refused"] > 0
        assert driver["sequences_mismatched_on_device"] == 0
        assert driver["pool_slots_differing_from_reference"] == 0
        assert counted["kv_seq_refused"] > 0
        assert result["failed"] <= result["attempted"]


def test_the_sound_run_hands_every_sequence_over_and_matches_the_reference(
        tiny):  # noqa: F811
    result, notes = _rehearse(tiny, CELL, seed=2**31 + 11)
    driver = next(n for n in notes if n["note"] == "driver")
    assert result["correct"] is True and result["failed"] == 0
    assert driver["transport"] == driver["transport_expected"] == "shm_ring"
    assert driver["whole_pools_compared"] is True
    assert driver["sequences_produced"] >= result["attempted"]
    assert driver["sequences_refused"] == 0
    # The rehearsal keeps the snapshot record at its real size.
    assert driver["snapshot_record_bytes"] == 2170880 > THRESHOLD
    records = driver["page_records"] + driver["snapshot_records"]
    counted = next(n for n in notes if n["note"] == "counters")
    # Three batch registry RPCs and one round of fetches a sequence,
    # each carrying every record of both kinds.
    assert counted["kv_reg_many_records"] == (
        records * counted["kv_reg_many_total"])
    assert counted["kv_reg_many_total"] == pytest.approx(
        3 * counted["kv_seq_total"], abs=3)
    assert counted["kv_fetch_total"] == records * counted["kv_seq_total"]
    assert counted["kv_seq_snapshot_bytes"] == (
        counted["kv_seq_snapshot_records"] * 2170880)
    # Every snapshot record went one of the large ways, here one-sided.
    assert counted["rma_tx_bytes"] == counted["kv_seq_snapshot_bytes"]
    assert "kv_seq_refused" not in counted      # zero deltas are left out
    # Same seed, same sequence; another seed, another.
    again, again_notes = _rehearse(tiny, CELL, seed=2**31 + 11)
    other, other_notes = _rehearse(tiny, CELL, seed=8)

    def checksum(these):
        return next(n for n in these if n["note"] == "driver")[
            "seed_checksum"]

    assert checksum(again_notes) == driver["seed_checksum"]
    assert checksum(other_notes) != driver["seed_checksum"]
    assert again["correct"] is True and other["correct"] is True


def test_a_state_written_to_another_slot_fails_the_end_of_window_checksums(
        tiny, monkeypatch):  # noqa: F811
    """Every hand-over's bytes are right and land in the decode pools,
    but from the 12th state write on a state lands one slot further:
    the on-device compare reads the slot it was meant for, and what the
    state pool holds at the end differs from the reference's."""
    from brpc_tpu.models import kv_pool

    real = kv_pool.write_page
    writes = 0

    def shifted(pool, slot, page):
        nonlocal writes
        writes += 1
        if writes >= 12:
            slot = (slot + 1) % pool.shape[0]
        return real(pool, slot, page)

    monkeypatch.setattr(kv_pool, "write_page", shifted)
    result, notes = _rehearse(tiny, CELL)
    driver = next(n for n in notes if n["note"] == "driver")
    assert writes > 20
    assert driver["pool_slots_differing_from_reference"] >= 1
    assert result["correct"] is False and result["failed"] > 0


def test_the_reference_followed_in_checksums_is_the_reference_held_whole():
    import jax.numpy as jnp

    rng = np.random.default_rng(31)

    def bits(*shape):
        return jnp.asarray(rng.integers(0, 1 << 16, shape, dtype=np.uint16))

    pools = {"prefill_pages": bits(7, 2, 4, 6),
             "prefill_states": bits(3, 2, 8, 4),
             "decode_pages": bits(7, 2, 4, 6),
             "decode_states": bits(3, 2, 8, 4)}
    first = (pools["prefill_pages"][:3], pools["prefill_states"][0])
    steps = [((0, 2, 4), 1, (5, 1, 3), 2, True),
             ((1, 2, 3), 0, (0, 1, 2), 0, False),
             ((5, 4, 0), 2, (2, 4, 6), 1, True),
             ((6, 1, 0), 2, (2, 3, 0), 1, True)]
    whole = reference_kv_hybrid.kv_hybrid_reference(pools, first, steps)

    def sums(pool):
        return [int(reference_kv.page_checksum(slot)) for slot in pool]

    followed = reference_kv_hybrid.kv_hybrid_reference_checksums(
        {name: sums(pool) for name, pool in pools.items()},
        reference_kv_hybrid.sequence_checksums(*first),
        2 * 4 * 6 // 2, 2 * 8 * 4 // 2, steps)
    assert followed == {name: sums(pool) for name, pool in whole.items()}
    # The second sequence was not handed over: neither decode pool has it.
    second = reference_kv_hybrid.next_sequence(
        *reference_kv_hybrid.next_sequence(*first))
    assert not any(np.array_equal(slot, second[1])
                   for slot in whole["decode_states"])
    assert np.array_equal(whole["prefill_states"][0], second[1])
    # A fresh sequence differs from the one before in every 32-bit word,
    # pages and states: a stale snapshot cannot pass for a fresh one.
    third = reference_kv_hybrid.next_sequence(*second)
    for a, b in ((first, second), (second, third), (first, third)):
        assert bool(jnp.all(reference_kv.page_words(a[1])
                            != reference_kv.page_words(b[1])))
        assert bool(jnp.all(
            reference_kv.page_words(a[0].reshape(-1, 4, 6))
            != reference_kv.page_words(b[0].reshape(-1, 4, 6))))
    assert second[0].shape == first[0].shape
    assert second[1].dtype == first[1].dtype


def test_the_timed_mix_is_the_configurations_widths_and_every_layer():
    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    cfg, mix = cell.config, cell.traffic
    g = manifest.driver("kv_seq_pull").geometry(cfg, mix)
    assert g["paged_layers"] == cfg["full_attn_layers"] == cfg[
        "linear_attn_config"]["full_attn_layers"]
    assert g["snapshot_layers"] == cfg["kda_layers"]
    assert sorted(g["paged_layers"] + g["snapshot_layers"]) == list(
        range(1, cfg["num_hidden_layers"] + 1))
    assert (g["page_record_bytes"], g["snapshot_record_bytes"]) == (
        147456, 2170880) == (cfg["page_record_bytes"],
                             cfg["snapshot_record_bytes"])
    assert g["snapshot_record_bytes"] - THRESHOLD == 73728
    assert (g["page_records"], g["snapshot_records"]) == (56, 20)
    assert g["bytes_per_call"] == 51675136 == cfg["sequence_bytes"]
    assert (mix["pool_pages"], mix["state_slots"]) == (
        cfg["pool_pages"], cfg["state_slots"])
    assert 2 * (mix["pool_pages"] * 7 * 147456
                + mix["state_slots"] * 20 * 2170880) == cfg["device_bytes"]
    assert cfg["reduced"] == [] and mix["sequences_in_flight"] == 2
    # Every number of the catalog's row for the model is in the file.
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(line) for line in catalog.read_text().split(
            "\n") if '"Kimi-Linear-48B-A3B-Instruct"' in line)
        assert cfg["model_config"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"]} == row["config"]
    with pytest.raises(ValueError, match="disagree"):
        manifest.driver("kv_seq_pull").geometry(
            cfg, dict(mix, snapshot_record_bytes=THRESHOLD))


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_reads_nothing(name):
    manifest = Manifest(ROOT)
    reader = manifest.reader(name)
    assert reader.DRIVERS == ("kv_seq_pull",)
    empty = types.SimpleNamespace(
        counters={}, spans=Spans(), trace=None, t_open=0.0, t_close=1.0,
        call_s=[], bytes_per_call=51675136, device_kind="TPU v5 lite",
        notes={})
    assert reader.read(empty) is None
    entry = next(m for m in manifest.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["unit"] == reader.UNIT


def test_the_readers_divide_what_the_window_counted():
    spans = Spans()
    for seq in range(4):
        at = 0.1 * seq
        spans.add("d2h", at, at + 0.001)
        spans.add("d2h_wait", at + 0.001, at + 0.009)
        spans.add("publish", at + 0.009, at + 0.016)
        spans.add("register", at + 0.015, at + 0.016)
        spans.add("fetch", at + 0.016, at + 0.026)
        spans.add("lookup", at + 0.016, at + 0.017)
        spans.add("h2d", at + 0.026, at + 0.036)
        spans.add("evict", at + 0.040, at + 0.041)
    ev = types.SimpleNamespace(
        spans=spans, trace=None, t_open=0.0, t_close=1.0, call_s=[0.05] * 4,
        bytes_per_call=50_000_000, device_kind="TPU v5 lite",
        notes={"page_records": 56, "page_record_bytes": 147456,
               "snapshot_records": 20, "snapshot_record_bytes": 2170880},
        counters={"rma_tx_bytes": 30e6, "stripe_tx_bytes": 10e6,
                  "batch_resp_bytes": 200e6, "batch_land_copy_bytes": 32e6})

    def read(name):
        return Manifest(ROOT).reader(name).read(ev)

    assert read("kvh_publish_us") == pytest.approx(15000.0)
    assert read("kvh_h2d_rate") == pytest.approx(5.0)
    assert read("kvh_registry_us") == pytest.approx(3000.0)
    assert read("kvh_fetch_us") == pytest.approx(9000.0)
    assert read("kvh_one_sided_share") == 75.0
    assert read("kvh_land_copy_share") == 16.0
    roofline = Manifest(ROOT).reader("kvh_pool_roofline")
    pages, states = 56 * 147456, 20 * 2170880
    assert roofline.program_hbm_bytes(pages, states, (2, 0)) == 2 * pages
    assert roofline.program_hbm_bytes(pages, states, (3, 3)) == 3 * (
        pages + states)
    ev.trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_bm_kvh_produce(1)", 100, 400000],
                ["jit_kv_read_pages(2)", 500000, 40000],
                ["jit_kv_read_page(3)", 600000, 160000],
                ["jit_kv_write_pages(4)", 800000, 40000],
                ["jit_kv_write_page(5)", 900000, 160000],
                ["jit_bm_kvh_verify(6)", 1100000, 150000]]},
            {"name": "XLA Ops", "events": [["%fusion.1 = x", 100, 60000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bm:produce", 0, 1000], ["bm:verify", 1100000, 200000]]}]}]}
    # 3 (P + S) + 4 P + 4 S in 800 us; `read_page` is not `read_pages`.
    assert read("kvh_pool_roofline") == pytest.approx(
        100 * 7 * (pages + states) / 800e-6 / 819e9)
    assert read("kvh_pool_roofline") < 100


def control_on_the_chip(fault: str, seed: int, seconds: float) -> dict:
    """One run of the cell at the timed sizes with `fault` in the node
    channel's pipeline from 6 sequences into the window on; the result
    line, whose `correct` must be false."""
    from benchmark import run
    from brpc_tpu.rpc import Channel

    manifest = Manifest(ROOT)
    after = 6 + int(manifest.cell(CELL).traffic["warm_calls"])
    real = Channel.pipeline
    Channel.pipeline = lambda self: FaultyKvSeqPipeline(
        real(self), fault, after)
    try:
        result, notes = run.run_cell(manifest, CELL, seed, seconds, False)
    finally:
        Channel.pipeline = real
    for note in notes:
        if note["note"] == "driver":
            print(json.dumps(note), flush=True)
    return result


if __name__ == "__main__":
    line = control_on_the_chip(sys.argv[1], int(sys.argv[2]),
                               float(sys.argv[3]))
    print(json.dumps(line), flush=True)
    sys.exit(0 if line["correct"] is False and line["failed"] > 0 else 1)
