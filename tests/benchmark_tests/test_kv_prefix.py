"""Driver `kv_prefix` on the CPU at its rehearsal sizes: the sound run
against the reference (depths, bytes, pool checksums, budgets, the three
identities of the counters), its control (a block served with one word
changed, a chain with a hole served as a run, a dropped block served from
stale bytes: each must read `correct` false), the mix against the
configuration's widths, the reference's own properties, and the readers
on made-up evidence and on the trace recorded on the chip.  Nothing here
is a measurement.

On the chip the control is `control_on_the_chip` below, run as
`python3 tests/benchmark_tests/test_kv_prefix.py <fault> <seed>
<seconds>` from the root of a checkout: the same faults under
`run.run_cell` at the timed sizes."""

import gzip
import json
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(pathlib.Path(__file__).parent)]

from benchmark import reference_kv, reference_kv_prefix as ref  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.spans import Spans  # noqa: E402
from test_rehearsal import _rehearse, tiny  # noqa: E402,F401  (fixture)

CELL = "kv_prefix.sessions6_zipf"
READERS = ("kvp_match_us", "kvp_fetch_us", "kvp_one_sided_share",
           "kvp_land_copy_share", "kvp_h2d_rate", "kvp_publish_us",
           "kvp_publish_copy_share", "kvp_hit_share", "kvp_cold_hit_share",
           "kvp_lock_wait_us", "kvp_pool_roofline")
FAULTS = ("one_word_changed", "hole_served_as_a_run",
          "dropped_block_served_stale")
CHECKS = ("pages_mismatched_on_device", "pool_slots_differing_from_reference",
          "turns_refused", "runs_over_published", "runs_under_reference",
          "runs_with_a_hole", "budget_passed", "served_not_hot_plus_cold",
          "promotes_over_cold_hits", "pages_not_restored_plus_prefilled")


class FaultyPrefixPipeline:
    """A node pipeline over a transport that, from its `after`-th submit
    on, fails the fetch of a window in one of two ways: one 32-bit word
    of the second block that landed is changed; or a block the store had
    dropped (its fetch answered kv-stale) is served all the same, from
    the bytes its place in the landing area held before."""

    def __init__(self, real, fault: str, after: int):
        self._real, self._fault, self._after = real, fault, after
        self._submits = 0
        self._places: dict = {}      # token -> its place

    def submit(self, method, requests, resp_bufs=None, **kw):
        self._submits += 1
        tokens = self._real.submit(method, requests, resp_bufs=resp_bufs,
                                   **kw)
        if self._submits > self._after:
            self._places.update(zip(tokens, resp_bufs))
        return tokens

    def poll(self, **kw):
        from brpc_tpu.rpc.batch import Completion

        done = self._real.poll(**kw)
        for i, c in enumerate(done):
            place = self._places.pop(c.token, None)
            if place is None:
                continue
            if self._fault == "one_word_changed" and c.ok:
                place.view(np.uint32)[len(place) // 8] ^= 1
                self._places.clear()         # one block a window
            elif (self._fault == "dropped_block_served_stale"
                  and not c.ok and "kv-stale" in c.error):
                done[i] = Completion(c.token, 0, "", len(place), True, None)
        return done

    def close(self):
        self._real.close()


def holed_match(real, after: int):
    """`KvRegistryClient.match` that, from its `after`-th call on, leaves
    the second matched block out of an answer of three or more: what is
    left is served as a run."""
    calls = 0

    def match(self, keys):
        nonlocal calls
        calls += 1
        records = real(self, keys)
        chain = []
        for r in records:
            if r.key not in chain:
                chain.append(r.key)
        if calls > after and len(chain) >= 3:
            records = [r for r in records if r.key != chain[1]]
        return records

    return match


def inject(patch, fault: str, after: int) -> None:
    """`patch(owner, name, value)` sets the faulty part in place."""
    from brpc_tpu.rpc import Channel, kv

    if fault == "hole_served_as_a_run":
        patch(kv.KvRegistryClient, "match",
              holed_match(kv.KvRegistryClient.match, after))
    else:
        real = Channel.pipeline
        # Windows, not turns, are submits: a turn has at least one.
        patch(Channel, "pipeline", lambda self: FaultyPrefixPipeline(
            real(self), fault, 2 * after))


def _turns_before_the_window(mix: dict) -> int:
    return int(mix["warm_sessions"]) * int(mix["turns"]) + 6


@pytest.mark.parametrize("fault", FAULTS)
def test_a_restore_with_a_block_wrong_fails_the_run(
        tiny, monkeypatch, fault):  # noqa: F811
    """The cell's control: the guarantee broken is that a restored page
    is byte-exact against the page published under that chain key, that
    a run is contiguous from block 0, and that a dropped block is never
    served.  Only because any two pages differ in every word do bytes
    left in the landing area by another block fail the compare."""
    inject(monkeypatch.setattr, fault,
           _turns_before_the_window(tiny.cell(CELL).traffic))
    result, notes = _rehearse(tiny, CELL)
    driver = next(n for n in notes if n["note"] == "driver")
    assert result["attempted"] > 6
    assert result["correct"] is False and result["failed"] > 0
    assert result["compared"]["failed_calls"] == {
        "value": result["failed"], "limit": 0}
    assert driver["pages_mismatched_on_device"] > 0
    assert result["compared"]["pages_mismatched_on_device_differs"] == {
        "value": 1, "limit": 0}
    assert (driver["runs_with_a_hole"] > 0) == (
        fault == "hole_served_as_a_run")


def test_the_sound_run_restores_inside_the_band_and_matches_the_reference(
        tiny):  # noqa: F811
    result, notes = _rehearse(tiny, CELL, seed=2**31 + 37)
    driver = next(n for n in notes if n["note"] == "driver")
    counted = next(n for n in notes if n["note"] == "counters")
    assert result["correct"] is True and result["failed"] == 0
    assert driver["transport"] == driver["transport_expected"] == "shm_ring"
    for name in CHECKS:
        assert driver[name] == 0 == driver[name + "_expected"]
        assert result["compared"][name + "_differs"] == {
            "value": 0, "limit": 0}
    pages = driver["pages"]
    assert pages["restored"] + pages["prefilled"] == pages["asked"]
    assert 0 < pages["restored"] <= pages["matched"] <= pages["asked"]
    # The window worked both tiers and passed the total budget.
    for name in ("kv_prefix_hot_hits", "kv_prefix_cold_hits",
                 "kv_prefix_promote", "kv_prefix_demote",
                 "kv_prefix_dropped", "kv_prefix_fetch_stale"):
        assert counted[name] > 0, name
    assert counted["kv_prefix_fetch_total"] == (
        counted["kv_prefix_hot_hits"] + counted["kv_prefix_cold_hits"])
    assert counted["kv_prefix_promote"] <= counted["kv_prefix_cold_hits"]
    # One Match a turn; one PutPrefixMany a published piece, every
    # record of it in that one round trip; on the CPU every source is
    # copied (dlpack imports the array: no landing block).
    assert counted["kv_prefix_match_total"] == result["attempted"]
    assert counted["kv_prefix_put_many_records"] == (
        counted["kv_prefix_publish_total"]
        + counted.get("kv_prefix_publish_renewed", 0))
    assert counted["kv_prefix_publish_copy_bytes"] == (
        counted["kv_prefix_publish_bytes"])
    assert "kv_prefix_publish_in_place_bytes" not in counted
    assert counted["kv_prefix_publish_bytes"] == (
        counted["kv_prefix_publish_total"] * driver["block_bytes"])
    # `goodput` is the restored bytes over the window.
    assert driver["restored_bytes_in_window"] > 0
    # The policy's own hit share, and a chain-aware one's, beside it.
    assert 0 < driver["hit_share_lru_model"] <= 100
    assert 0 < driver["hit_share_chain_aware_model"] <= 100
    # Same seed, same turns; another seed, others.
    again, again_notes = _rehearse(tiny, CELL, seed=2**31 + 37)
    other, other_notes = _rehearse(tiny, CELL, seed=9)

    def checksum(these):
        return next(n for n in these if n["note"] == "driver")[
            "seed_checksum"]

    assert checksum(again_notes) == driver["seed_checksum"]
    assert checksum(other_notes) != driver["seed_checksum"]
    assert again["correct"] is True and other["correct"] is True


def test_a_program_without_the_prefix_path_is_refused_before_set_up(
        tiny, monkeypatch):  # noqa: F811
    """What the parent commit is under this PR's benchmark files: the
    cell fails at once, with another exit code than 0, and says why."""
    from brpc_tpu.rpc import kv

    monkeypatch.delattr(kv, "publish_prefix_run")
    monkeypatch.delattr(kv.KvClient, "fetch_prefix_blocks")
    with pytest.raises(SystemExit, match="publish_prefix_run, "
                       "fetch_prefix_blocks of brpc_tpu.rpc.kv"):
        _rehearse(tiny, CELL)


def test_a_page_written_to_another_slot_fails_the_end_of_window_checksums(
        tiny, monkeypatch):  # noqa: F811
    """Every restored block's bytes are right, but from the 40th write on
    a run lands one slot further: the on-device compare reads the slots
    it was meant for, and what the admitting pool holds at the end
    differs from the reference's."""
    from brpc_tpu.models import kv_pool

    real = kv_pool.write_pages
    writes = 0

    def shifted(pool, slots, pages):
        nonlocal writes
        writes += 1
        if writes >= 40:
            slots = (slots + 1) % pool.shape[0]
        return real(pool, slots, pages)

    monkeypatch.setattr(kv_pool, "write_pages", shifted)
    result, notes = _rehearse(tiny, CELL)
    driver = next(n for n in notes if n["note"] == "driver")
    assert writes > 60
    assert driver["pool_slots_differing_from_reference"] >= 1
    assert result["correct"] is False and result["failed"] > 0


def test_the_reference_orders_turns_names_pages_and_follows_checksums():
    import jax.numpy as jnp

    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "sessions6_zipf.json").read_text())
    turns = ref.kv_prefix_reference(5, mix, 600)
    assert turns == ref.kv_prefix_reference(5, mix, 600)
    assert turns != ref.kv_prefix_reference(6, mix, 600)
    # Slot 1 takes 41 % of the turns, slot 6 7 % (weights 1/k).
    by_slot = [sum(t.slot == k for t in turns) / 600 for k in range(6)]
    assert 0.33 < by_slot[0] < 0.49 and 0.03 < by_slot[5] < 0.12
    last = {}
    for t in turns:
        assert t.number == last.get(t.session, 0) + 1 <= mix["turns"]
        last[t.session] = t.number
        doc = t.prompt_pages - 8 - 2 * (t.number - 1)
        assert doc in mix["doc_pages"]
        ids = t.page_ids()
        assert ids[:8] == [(ref.SYSTEM, i) for i in range(8)]
        assert ids[8:] == [(t.session, i) for i in range(8, t.prompt_pages)]
    # Every eight sessions take the table's eight documents once.
    docs = {t.session: t.prompt_pages - 8 for t in turns if t.number == 1}
    assert sorted(docs[s] for s in range(8)) == sorted(mix["doc_pages"])
    assert [docs[s] for s in range(8)] == [docs[s] for s in range(8, 16)]
    # A later turn's prompt begins with the earlier turn's, every
    # session's with the system prompt, and no two sessions share more.
    first = next(t for t in turns if t.number == 1)
    second = next(t for t in turns
                  if t.session == first.session and t.number == 2)
    other = next(t for t in turns if t.session != first.session)
    a, b, c = (ref.tokens(5, t, 4, 1000) for t in (first, second, other))
    assert b[:len(a)] == a and len(b) == len(a) + 2 * 4
    assert c[:8 * 4] == a[:8 * 4] and c[8 * 4:9 * 4] != a[8 * 4:9 * 4]
    assert all(0 <= t < 1000 for t in a)
    # A page is the base page with its constant on every word, and its
    # checksum follows in integers.
    rng = np.random.default_rng(37)
    base = jnp.asarray(rng.integers(0, 1 << 16, (2, 4, 6), dtype=np.uint16))
    consts = np.asarray([ref.page_const(5, ref.SYSTEM, 0),
                         ref.page_const(5, 3, 9)], dtype=np.uint32)
    pages = ref.next_pages(base, jnp.asarray(consts))
    assert pages.shape == (2, 2, 4, 6) and pages.dtype == base.dtype
    base_sum = int(reference_kv.page_checksum(base))
    for page, const in zip(pages, consts):
        assert int(const) & 1
        assert int(reference_kv.page_checksum(page)) == ref.page_sum(
            base_sum, 2 * 4 * 6 // 2, int(const))
    assert bool(jnp.all(reference_kv.page_words(pages[0])
                        != reference_kv.page_words(pages[1])))
    sums = ref.PoolSums(5, base_sum, 24, [1, 2, 3], [4, 5, 6])
    sums.write("admitting", [2, 0], [(ref.SYSTEM, 0), (3, 9)])
    assert sums.sums["producing"] == [1, 2, 3]
    assert sums.sums["admitting"] == [
        ref.page_sum(base_sum, 24, int(consts[1])), 5,
        ref.page_sum(base_sum, 24, int(consts[0]))]


def test_the_store_model_demotes_promotes_and_drops_in_touch_order():
    store = ref.StoreModel(total_blocks=4, hot_blocks=2)
    blocks = [(0, i) for i in range(6)]
    for b in blocks[:4]:
        assert store.publish(b) is True
    # Two hot, two demoted; nothing dropped yet.
    assert list(store.hot) == blocks[2:4] and list(store.cold) == blocks[:2]
    assert store.depth(blocks) == 4
    # Renewed: a touch, and a touch leaves a block hot, a publisher's as
    # a fetch's (left in the heap tier it would go before every hot one).
    assert store.publish(blocks[1]) is False
    assert list(store.hot) == [blocks[3], blocks[1]]
    assert list(store.cold) == [blocks[0], blocks[2]]
    assert store.fetch(blocks[0]) is True             # cold hit: promoted
    assert list(store.hot) == [blocks[1], blocks[0]]
    assert list(store.cold) == [blocks[2], blocks[3]]
    store.publish(blocks[4])       # the total is passed: LRU cold drops
    assert blocks[2] not in store and store.depth(blocks) == 2
    assert store.fetch(blocks[2]) is False
    assert store.counts["dropped"] == 1 and store.counts["promote"] == 1
    assert store.counts["renewed"] == store.counts["renew_promote"] == 1
    assert store.counts["stale"] == 1 and len(store) == 4
    # One order by last touch: the hot tier is its newest end.
    assert list(store.cold) + list(store.hot) == [
        blocks[3], blocks[1], blocks[0], blocks[4]]
    # The band: at most what was ever published, at least what is held.
    assert ref.depth_band(store, blocks) == (2, 5)
    # A chain-aware store drops the tail of the least recent chain.
    aware = ref.StoreModel(3, 3, chain_aware=True)
    for b in [(ref.SYSTEM, 0), (7, 1), (7, 2)]:
        aware.publish(b)
    aware.publish((8, 1))
    assert (7, 2) not in aware and (7, 1) in aware and (ref.SYSTEM, 0) in aware
    # Driving itself, a store that holds everything misses only what no
    # turn before has published.
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "sessions6_zipf.json").read_text())
    everything = ref.self_driven_hit_share(5, mix, 300, 10**6, 10**6, False)
    real = ref.self_driven_hit_share(5, mix, 300, 477, 238, False)
    assert 60 < real <= everything < 85


def test_the_timed_mix_is_the_configurations_widths_and_every_layer():
    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    cfg, mix = cell.config, cell.traffic
    driver = manifest.driver("kv_prefix")
    g = driver.geometry(cfg, mix)
    assert g["page_shape"] == (61, 128, 576) == (
        cfg["num_hidden_layers"], mix["page_tokens"],
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    assert g["block_bytes"] == 8994816 == cfg["block_bytes"] == (
        61 * cfg["record_bytes"])
    assert mix["pool_pages"] * g["block_bytes"] == cfg["pool_bytes"] == (
        5756682240)
    assert (g["hot_bytes"], g["store_bytes"]) == (2 << 30, 4 << 30)
    assert ref.blocks_of(g["hot_bytes"], g["block_bytes"]) == 238
    assert ref.blocks_of(g["store_bytes"], g["block_bytes"]) == 477
    assert g["longest_prompt"] == 110 <= mix["pool_pages"]
    # A window in flight fits the connection's one-sided window.
    assert g["window"] * g["block_bytes"] < 256 << 20
    assert cfg["reduced"] == [] and cell.chips == 1
    assert driver.pieces(13, 16) == [4, 4, 4, 1]
    assert driver.pieces(96, 16) == [16] * 6
    # Every number of the catalog's row for the model is in the file.
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(line) for line in catalog.read_text().split(
            "\n") if '"Kimi-K2-Instruct"' in line)
        assert cfg["model_config"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"]} == row["config"]
    with pytest.raises(ValueError, match="disagree"):
        driver.geometry(cfg, dict(mix, block_bytes=147456))
    for window in (12, 8):
        with pytest.raises(ValueError, match="disagree"):
            driver.geometry(cfg, dict(mix, fetch_window_pages=window))


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_reads_nothing(name):
    manifest = Manifest(ROOT)
    reader = manifest.reader(name)
    assert reader.DRIVERS == ("kv_prefix",)
    empty = types.SimpleNamespace(
        counters={}, spans=Spans(), trace=None, t_open=0.0, t_close=1.0,
        call_s=[], bytes_per_call=0.0, device_kind="TPU v5 lite", notes={})
    assert reader.read(empty) is None
    entry = next(m for m in manifest.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["unit"] == reader.UNIT


def _made_up_evidence():
    spans = Spans()
    for turn in range(4):
        at = 0.2 * turn
        spans.add("match", at, at + 0.0004 * (turn + 1))
        spans.add("fetch", at + 0.002, at + 0.022)
        spans.add("h2d", at + 0.022, at + 0.047)
        spans.add("d2h_wait", at + 0.06, at + 0.07)
        spans.add("publish", at + 0.07, at + 0.1)
        spans.add("put", at + 0.099, at + 0.1)
    return types.SimpleNamespace(
        spans=spans, trace=None, t_open=0.0, t_close=1.0,
        call_s=[0.05] * 4, bytes_per_call=125e6,
        device_kind="TPU v5 lite",
        notes={"block_bytes": 8994816,
               "traced": {"produce_runs": 2, "pages_produced": 18,
                          "pages_read": 18, "pages_written": 40}},
        counters={"rma_tx_bytes": 30e6, "stripe_tx_bytes": 10e6,
                  "batch_resp_bytes": 200e6, "batch_land_copy_bytes": 150e6,
                  "kv_prefix_fetch_total": 40.0, "kv_prefix_hot_hits": 30.0,
                  "kv_prefix_cold_hits": 10.0, "kv_prefix_match_keys": 50.0,
                  "kv_prefix_lock_wait_us": 120.0,
                  "kv_prefix_publish_total": 8.0,
                  "kv_prefix_publish_renewed": 2.0,
                  "kv_prefix_publish_copy_bytes": 1e6,
                  "kv_prefix_publish_in_place_bytes": 3e6})


def test_the_readers_divide_what_the_window_counted():
    ev = _made_up_evidence()

    def read(name):
        return Manifest(ROOT).reader(name).read(ev)

    assert read("kvp_match_us") == pytest.approx(1000.0)
    assert read("kvp_fetch_us") == pytest.approx(2000.0)
    assert read("kvp_one_sided_share") == 75.0
    assert read("kvp_land_copy_share") == 75.0
    assert read("kvp_h2d_rate") == pytest.approx(5.0)
    assert read("kvp_publish_us") == pytest.approx(16000.0)
    assert read("kvp_publish_copy_share") == 25.0
    assert read("kvp_hit_share") == 80.0
    assert read("kvp_cold_hit_share") == 25.0
    assert read("kvp_lock_wait_us") == 3.0
    assert read("kvp_pool_roofline") is None          # no trace
    from benchmark import work_kv_prefix as work

    assert work.produce_hbm_bytes(18, 2, 100) == (2 + 36) * 100
    assert work.read_pages_hbm_bytes(18, 100) == 3600
    assert work.write_pages_hbm_bytes(40, 100) == 8000
    ev.trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_bm_kvp_produce(1)", 100, 600000],
                ["jit_kv_read_pages(2)", 700000, 500000],
                ["jit_kv_write_pages(4)", 1300000, 900000],
                ["jit_kv_read_page(3)", 2300000, 160000],
                ["jit_bm_kvp_verify(6)", 2500000, 150000]]},
            {"name": "XLA Ops", "events": [["%fusion.1 = x", 100, 60000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bm:produce", 0, 1000], ["bm:verify", 2500000, 200000]]}]}]}
    moved = (2 + 36 + 36 + 80) * 8994816
    assert read("kvp_pool_roofline") == pytest.approx(
        100 * moved / 2000e-6 / 819e9)
    assert read("kvp_pool_roofline") < 100


def test_the_readers_read_the_trace_recorded_on_the_chip():
    """`benchmark/testdata/trace_kv_prefix.json.gz` is a cut of a traced
    run of the cell on a TPU v5e: the pool programs' modules are in it,
    and the share of the roofline they reach lies under 100 %."""
    manifest = Manifest(ROOT)
    with gzip.open(manifest.home / "testdata" / "trace_kv_prefix.json.gz",
                   "rt") as f:
        trace = json.load(f)
    from benchmark import trace_reduce

    runs = {name: trace_reduce.count_by_name(
        trace, trace_reduce.MODULE_LINE, pattern)
        for name, pattern in zip(
            ("produce", "read", "write"),
            manifest.reader("kvp_pool_roofline").MODULES)}
    assert all(n > 0 for n in runs.values()), runs
    ev = _made_up_evidence()
    ev.trace = trace
    # The pages a run of each program moved are the driver's count (a
    # run moves 1 to 16); at one page a run the share is the least the
    # recorded times allow.
    ev.notes["traced"] = {"produce_runs": runs["produce"],
                          "pages_produced": runs["produce"],
                          "pages_read": runs["read"],
                          "pages_written": runs["write"]}
    assert 0 < manifest.reader("kvp_pool_roofline").read(ev) < 100
    assert 0 < manifest.reader("device_idle_share").read(ev) < 100


def control_on_the_chip(fault: str, seed: int, seconds: float) -> dict:
    """One run of the cell at the timed sizes with `fault` from 6 turns
    into the window on; the result line, whose `correct` must be
    false."""
    from benchmark import run

    manifest = Manifest(ROOT)
    undo = []

    def patch(owner, name, value):
        undo.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    inject(patch, fault, _turns_before_the_window(manifest.cell(CELL).traffic))
    try:
        result, notes = run.run_cell(manifest, CELL, seed, seconds, False)
    finally:
        for owner, name, value in undo:
            setattr(owner, name, value)
    for note in notes:
        if note["note"] == "driver":
            print(json.dumps(note), flush=True)
    return result


if __name__ == "__main__":
    line = control_on_the_chip(sys.argv[1], int(sys.argv[2]),
                               float(sys.argv[3]))
    print(json.dumps(line), flush=True)
    sys.exit(0 if line["correct"] is False and line["failed"] > 0 else 1)
