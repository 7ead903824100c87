"""The KV plane for a cache of two kinds (ISSUE 31): a layout of paged
layers and snapshot layers, a sequence's hand-over as one unit
(`publish_sequence` / `KvClient.fetch_sequence` / `withdraw_sequence`),
records on both sides of `trpc_stripe_threshold` in one round, against
the plain reference (benchmark/reference_kv_hybrid.py), and the page
trio as the layout of one kind with one page.  Small pools, seeded;
nothing here is a measurement."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_kv_hybrid
from brpc_tpu.models import kv_pool
from brpc_tpu.rpc import Channel, RmaBuffer, Server, kv, observe

LEASE = 600000
THRESHOLD = 2 << 20                 # trpc_stripe_threshold's default
PAGE_RECORD = 128 * 576 * 2         # 147,456 B: a page of an MLA layer
SNAPSHOT_RECORD = 32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2   # 2,170,880 B
# Kimi-Linear's layers, 1-based as its config gives them.
FULL_ATTN = (4, 8, 12, 16, 20, 24, 27)
VARS = ("kv_seq_total", "kv_seq_page_records", "kv_seq_snapshot_records",
        "kv_seq_page_bytes", "kv_seq_snapshot_bytes", "kv_seq_refused",
        "kv_reg_many_total", "kv_reg_many_records", "kv_fetch_many_total",
        "kv_fetch_many_records", "kv_fetch_total", "rma_tx_bytes",
        "stripe_tx_bytes", "batch_resp_bytes", "batch_land_copy_bytes")


def _vars():
    dumped = observe.Vars.dump()
    return {k: dumped.get(k, 0) for k in VARS}


def _moved(before):
    return {k: v - before[k] for k, v in _vars().items()}


def kimi_linear_layout(page_record, snapshot_record):
    return kv.KvCacheLayout(
        tuple(kv.PAGED if layer in FULL_ATTN else kv.SNAPSHOT
              for layer in range(1, 28)),
        tuple(page_record if layer in FULL_ATTN else snapshot_record
              for layer in range(1, 28)))


@pytest.fixture(params=[True, False], ids=["shm", "tcp"])
def node(request):
    """One in-process prefill node (store and registry), a registry
    client and a decode-side client over the transport asked for."""
    kv.reset()
    srv = Server()
    srv.enable_kv_store()
    srv.enable_kv_registry()
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    reg = kv.KvRegistryClient(Channel(addr, timeout_ms=20000),
                              owns_channel=True)
    cli = kv.KvClient(addr, use_shm=request.param, timeout_ms=20000)
    yield addr, reg, cli
    cli.close()
    reg.close()
    srv.stop()
    kv.reset()


def test_a_layout_gives_both_sides_the_same_ids_and_sizes():
    layout = kimi_linear_layout(PAGE_RECORD, SNAPSHOT_RECORD)
    assert len(layout.layers_of(kv.PAGED)) == 7
    assert len(layout.layers_of(kv.SNAPSHOT)) == 20
    assert SNAPSHOT_RECORD == 2170880 > THRESHOLD > PAGE_RECORD
    assert layout.sequence_bytes(8) == 51675136
    paged, snapshot = layout.records(31, 8)
    assert len(paged) == 56 and len(snapshot) == 20
    assert {n for _, n in paged} == {PAGE_RECORD}
    assert {n for _, n in snapshot} == {SNAPSHOT_RECORD}
    # Page by page each paged layer, then each snapshot layer at the
    # boundary: the order the bytes lie in a slab and a landing area.
    assert [rid for rid, _ in paged] == [
        kv.sequence_record_id(31, layer - 1, page)
        for page in range(8) for layer in FULL_ATTN]
    assert [rid for rid, _ in snapshot] == [
        kv.sequence_record_id(31, layer - 1, 8)
        for layer in range(1, 28) if layer not in FULL_ATTN]
    assert layout.record_ids(31, 8) == [rid for rid, _ in paged + snapshot]
    # No id twice over sequences, lengths and layers; another boundary's
    # snapshot is another record, the pages before it are the same.
    ids = {(seq, pages): layout.record_ids(seq, pages)
           for seq in (1, 2, 31, (1 << 32) - 1) for pages in (1, 7, 8)}
    flat = [rid for group in ids.values() for rid in group]
    assert len(set(flat)) == sum(
        7 * max(p for s, p in ids if s == seq) + 20 * 3
        for seq in (1, 2, 31, (1 << 32) - 1))
    assert set(ids[31, 7][:49]) < set(ids[31, 8])
    assert not set(ids[31, 7][49:]) & set(ids[31, 8])
    # A lone page is a sequence of one page of a layout of one kind.
    lone = kv.KvCacheLayout.paged(61, PAGE_RECORD)
    assert lone.records(1 << 40, 1) == (
        [(kv.page_record_id(1 << 40, layer), PAGE_RECORD)
         for layer in range(61)], [])


@pytest.mark.parametrize("seq_id, layer, number", [
    (1 << 32, 0, 1), (-1, 0, 1), (5, 0, 1 << 15), (5, 0, -1),
    (5, (1 << 16) - 1, 0), (1 << 47, 0, 0)])
def test_an_id_out_of_range_is_refused(seq_id, layer, number):
    with pytest.raises(ValueError, match="no record id"):
        kv.sequence_record_id(seq_id, layer, number)


@pytest.mark.parametrize("kinds, sizes", [
    ((), ()), (("paged", "window"), (8, 8)), (("paged",), (8, 8)),
    (("snapshot",), (0,))])
def test_what_is_no_layout_is_refused(kinds, sizes):
    with pytest.raises(ValueError, match="not a cache layout"):
        kv.KvCacheLayout(kinds, sizes)


TOKENS, WIDTH, ROWS = 4, 16, 6
SMALL = dict(page=TOKENS * WIDTH * 2, snapshot=ROWS * 128 * 2)


def _small_pools(seed, pages=12, slots=4):
    return {
        "prefill_pages": kv_pool.seeded_pool(seed, pages, 7, TOKENS, WIDTH),
        "prefill_states": kv_pool.seeded_pool(seed + 1, slots, 20, ROWS,
                                              128),
        "decode_pages": kv_pool.seeded_pool(seed + 2, pages, 7, TOKENS,
                                            WIDTH),
        "decode_states": kv_pool.seeded_pool(seed + 3, slots, 20, ROWS,
                                             128)}


@pytest.mark.parametrize("seed", [1, 2])
def test_sequences_through_the_kv_plane_match_the_reference(node, seed):
    """Seeded sequences of 3 pages produced by the reference's rule,
    handed over through the plane or (every third) not, over shm and
    over tcp: the landed bytes of both kinds, the counters, and at the
    end all four pools, against the reference."""
    addr, reg, cli = node
    n = 3
    layout = kimi_linear_layout(SMALL["page"], SMALL["snapshot"])
    pools = _small_pools(seed)
    whole = {name: np.array(pool) for name, pool in pools.items()}
    first = (kv_pool.read_pages(pools["prefill_pages"], jnp.arange(n)),
             kv_pool.read_page(pools["prefill_states"], 0))
    rng = random.Random(seed)
    slab = RmaBuffer(layout.sequence_bytes(n))
    land = RmaBuffer(layout.sequence_bytes(n))
    steps, last = [], first
    before = _vars()
    try:
        for seq_id in range(1, 8):
            step = (tuple(rng.sample(range(12), n)), rng.randrange(4),
                    tuple(rng.sample(range(12), n)), rng.randrange(4),
                    seq_id % 3 != 0)
            steps.append(step)
            last = reference_kv_hybrid.next_sequence(*last)
            pools["prefill_pages"] = kv_pool.write_pages(
                pools["prefill_pages"], jnp.asarray(step[0]), last[0])
            pools["prefill_states"] = kv_pool.write_page(
                pools["prefill_states"], step[1], last[1])
            if not step[4]:
                continue
            metas = kv.publish_sequence(
                seq_id, layout,
                kv_pool.read_pages(pools["prefill_pages"],
                                   jnp.asarray(step[0])),
                kv_pool.read_page(pools["prefill_states"], step[1]),
                slab, lease_ms=LEASE, node=addr, registry=reg)
            assert [m.block_id for m in metas] == layout.record_ids(
                seq_id, n)
            assert [m.generation for m in metas] == [1] * (7 * n + 20)
            got = cli.fetch_sequence(seq_id, layout, *_landing(land, n))
            assert np.array_equal(got[0], last[0])
            assert np.array_equal(got[1], last[1])
            back = jax.device_put(got)
            pools["decode_pages"] = kv_pool.write_pages(
                pools["decode_pages"], jnp.asarray(step[2]), back[0])
            pools["decode_states"] = kv_pool.write_page(
                pools["decode_states"], step[3], back[1])
            kv.withdraw_sequence(seq_id, layout, n, registry=reg)
        assert kv.store_count() == 0 == kv.registry_count()
    finally:
        slab.free()
        land.free()
    want = reference_kv_hybrid.kv_hybrid_reference(
        {name: jnp.asarray(pool) for name, pool in whole.items()},
        first, steps)
    for name in pools:
        assert np.array_equal(pools[name], want[name]), name
    assert not np.array_equal(pools["decode_states"],
                              whole["decode_states"])
    handed, records = 5, 5 * (7 * n + 20)
    moved = _moved(before)
    assert moved["kv_seq_total"] == handed
    assert moved["kv_seq_refused"] == 0
    assert moved["kv_seq_page_records"] == 7 * n * handed
    assert moved["kv_seq_snapshot_records"] == 20 * handed
    assert moved["kv_seq_page_bytes"] == 7 * n * handed * SMALL["page"]
    assert moved["kv_seq_snapshot_bytes"] == 20 * handed * SMALL["snapshot"]
    # One register_many, one lookup_many, one evict_many and one round
    # of fetches a sequence, each carrying every record of both kinds.
    assert moved["kv_reg_many_total"] == 3 * handed
    assert moved["kv_reg_many_records"] == 3 * records
    assert moved["kv_fetch_many_total"] == handed
    assert moved["kv_fetch_total"] == records
    # Over tcp the node channel is pooled and holds no socket of its own.
    assert set(cli.transports().values()) == {
        "shm_ring" if cli._use_shm else ""}


def test_a_1k_prompt_of_kimi_linear_is_76_records_an_rpc(node):
    addr, reg, cli = node
    layout = kimi_linear_layout(SMALL["page"], SMALL["snapshot"])
    rng = np.random.default_rng(31)
    pages = rng.integers(0, 1 << 16, (8, 7, TOKENS, WIDTH), dtype=np.uint16)
    states = rng.integers(0, 1 << 16, (20, ROWS, 128), dtype=np.uint16)
    before = _vars()
    with RmaBuffer(layout.sequence_bytes(8)) as slab, \
            RmaBuffer(layout.sequence_bytes(8)) as land:
        kv.publish_sequence(9, layout, pages, states, slab, lease_ms=LEASE,
                            node=addr, registry=reg)
        area = np.frombuffer(land.view, np.uint16)
        got = cli.fetch_sequence(
            9, layout, area[:pages.size].reshape(pages.shape),
            area[pages.size:].reshape(states.shape))
        assert np.array_equal(got[0], pages)
        assert np.array_equal(got[1], states)
        kv.withdraw_sequence(9, layout, 8, registry=reg)
    moved = _moved(before)
    assert moved["kv_reg_many_total"] == 3
    assert moved["kv_reg_many_records"] == 3 * 76
    assert moved["kv_fetch_many_records"] == 76 == moved["kv_fetch_total"]
    assert (moved["kv_seq_total"], moved["kv_seq_page_records"],
            moved["kv_seq_snapshot_records"]) == (1, 56, 20)


def test_records_on_both_sides_of_the_threshold_land_in_one_round(node):
    """Snapshot records of 2,097,152 B (not over `trpc_stripe_threshold`:
    one frame), 2,097,153 B and Kimi-Linear's 2,170,880 B (over it: the
    one-sided window over shm, stripe frames over tcp) in one round
    beside page records of 147,456 B, each byte-exact in its place."""
    addr, reg, cli = node
    sizes = (THRESHOLD, PAGE_RECORD, THRESHOLD + 1, SNAPSHOT_RECORD,
             PAGE_RECORD)
    kinds = (kv.SNAPSHOT, kv.PAGED, kv.SNAPSHOT, kv.SNAPSHOT, kv.PAGED)
    layout = kv.KvCacheLayout(kinds, sizes)
    large = THRESHOLD + 1 + SNAPSHOT_RECORD
    rng = np.random.default_rng(7)
    pages = rng.integers(0, 256, (3, 2, PAGE_RECORD), dtype=np.uint8)
    states = rng.integers(0, 256, THRESHOLD + large, dtype=np.uint8)
    total = layout.sequence_bytes(3)
    assert total == pages.nbytes + states.nbytes
    before = _vars()
    with RmaBuffer(total) as slab, RmaBuffer(total) as land:
        metas = kv.publish_sequence(3, layout, pages, states, slab,
                                    lease_ms=LEASE, node=addr, registry=reg)
        assert [m.length for m in metas] == [PAGE_RECORD] * 6 + [
            THRESHOLD, THRESHOLD + 1, SNAPSHOT_RECORD]
        assert [m.off - metas[0].off for m in metas[6:]] == [
            pages.nbytes, pages.nbytes + THRESHOLD,
            pages.nbytes + 2 * THRESHOLD + 1]
        area = np.frombuffer(land.view, np.uint8)
        got = cli.fetch_sequence(
            3, layout, area[:pages.nbytes].reshape(pages.shape),
            area[pages.nbytes:])
        assert np.array_equal(got[0], pages)
        assert np.array_equal(got[1], states)
        kv.withdraw_sequence(3, layout, 3, registry=reg)
    moved = _moved(before)
    assert moved["batch_resp_bytes"] == total
    # A body goes one of the large ways only if it is over the
    # threshold: the record of exactly 2 MiB rides a frame.
    assert moved["rma_tx_bytes"] + moved["stripe_tx_bytes"] == large
    if cli._use_shm:
        assert moved["rma_tx_bytes"] == large
        # A region takes one record in place at a time (its header holds
        # one completion descriptor): the other large record crossed the
        # window and was copied out, as every frame's body was.
        assert moved["batch_land_copy_bytes"] in (
            total - SNAPSHOT_RECORD, total - THRESHOLD - 1)
    assert (moved["kv_seq_page_bytes"], moved["kv_seq_snapshot_bytes"]) == (
        pages.nbytes, states.nbytes)


def _published(addr, reg, layout, seq_id, n, slab, seed=5):
    rng = np.random.default_rng(seed)
    pages = rng.integers(0, 1 << 16, (n, 7, TOKENS, WIDTH), dtype=np.uint16)
    states = rng.integers(0, 1 << 16, (20, ROWS, 128), dtype=np.uint16)
    kv.publish_sequence(seq_id, layout, pages, states, slab, lease_ms=LEASE,
                        node=addr, registry=reg)
    return pages, states


def _landing(land, n):
    area = np.frombuffer(land.view, np.uint16)
    cut = n * 7 * TOKENS * WIDTH
    return (area[:cut].reshape(n, 7, TOKENS, WIDTH),
            area[cut:cut + 20 * ROWS * 128].reshape(20, ROWS, 128))


@pytest.mark.parametrize("fault", ["page_record_withdrawn",
                                   "snapshot_of_another_boundary",
                                   "snapshot_record_short"])
def test_a_hand_over_with_a_record_wrong_is_refused_whole(node, fault):
    addr, reg, cli = node
    layout = kimi_linear_layout(SMALL["page"], SMALL["snapshot"])
    kda = layout.layers_of(kv.SNAPSHOT)
    before = _vars()
    with RmaBuffer(2 * layout.sequence_bytes(3)) as slab, \
            RmaBuffer(layout.sequence_bytes(3)) as land:
        pages, states = _published(addr, reg, layout, 77, 3, slab)
        asked = 3
        if fault == "page_record_withdrawn":
            wrong = [kv.sequence_record_id(77, FULL_ATTN[2] - 1, 1)]
            kv.withdraw(wrong[0])
        elif fault == "snapshot_of_another_boundary":
            # The decode rank was told 2 pages: the states it asks for
            # are those taken after 2, which nobody published; those of
            # boundary 3 are there, and are not what it gets.
            asked = 2
            wrong = [kv.sequence_record_id(77, layer, 2) for layer in kda]
        else:
            # Layer 9's snapshot published again, cut short.
            wrong = [kv.sequence_record_id(77, kda[9], 3)]
            kv.withdraw(wrong[0])
            reg.evict(wrong[0])
            meta = kv.publish(wrong[0], slab,
                              offset=layout.sequence_bytes(3),
                              length=SMALL["snapshot"] - 256,
                              lease_ms=LEASE, node=addr)
            reg.register(meta, lease_ms=LEASE)
        np.frombuffer(land.view, np.uint8)[:] = 0
        with pytest.raises(kv.KvFetchManyError) as refusal:
            cli.fetch_sequence(77, layout, *_landing(land, asked))
        assert sorted(refusal.value.failed) == sorted(wrong)
        for record_id in wrong:
            assert str(record_id) in str(refusal.value)
        if fault == "snapshot_of_another_boundary":
            assert all(isinstance(e, kv.KvMissError)
                       for e in refusal.value.failed.values())
            # Never the bytes of the other boundary's snapshots.
            assert not _landing(land, asked)[1].any()
        elif fault == "snapshot_record_short":
            assert "record of 1280 bytes" in str(refusal.value)
        moved = _moved(before)
        assert (moved["kv_seq_refused"], moved["kv_seq_total"]) == (1, 0)
        assert moved["kv_seq_page_records"] == 0
        # Put right, the same sequence is handed over.
        if fault != "snapshot_of_another_boundary":
            for record_id in layout.record_ids(77, 3):
                cli.invalidate(record_id)
                try:
                    kv.withdraw(record_id)
                except kv.KvMissError:
                    pass
            reg.evict_many(layout.record_ids(77, 3))
            kv.publish_sequence(77, layout, pages, states, slab,
                                lease_ms=LEASE, node=addr, registry=reg)
        got = cli.fetch_sequence(77, layout, *_landing(land, 3))
        assert np.array_equal(got[0], pages)
        assert np.array_equal(got[1], states)
        assert _moved(before)["kv_seq_total"] == 1
        kv.withdraw_sequence(77, layout, 3, registry=reg)


def test_landing_areas_that_do_not_fit_the_sequence_are_refused(node):
    addr, reg, cli = node
    layout = kimi_linear_layout(SMALL["page"], SMALL["snapshot"])
    with RmaBuffer(layout.sequence_bytes(2)) as slab, \
            RmaBuffer(layout.sequence_bytes(2)) as land:
        pages, states = _published(addr, reg, layout, 5, 2, slab)
        into_pages, into_states = _landing(land, 2)
        with pytest.raises(ValueError, match="landing area"):
            cli.fetch_sequence(5, layout, into_pages, into_states[:-1])
        with pytest.raises(ValueError, match="landing area"):
            cli.fetch_sequence(5, layout, into_pages[:, ::2], into_states)
        with pytest.raises(ValueError, match="snapshot layers"):
            cli.fetch_sequence(5, layout, into_pages)
        with pytest.raises(ValueError, match="does not fit the slab"):
            kv.publish_sequence(6, layout, pages, states, slab, offset=2)
        with pytest.raises(ValueError, match="does not fit the slab"):
            kv.publish_sequence(6, layout, pages, states[:-1], slab)
        assert kv.store_count() == 2 * 7 + 20
        # A live sequence is refused whole and keeps its slab bytes.
        with pytest.raises(kv.KvExistsError):
            kv.publish_sequence(5, layout, pages, states, slab, node=addr)
        assert kv.store_count() == 2 * 7 + 20
        got = cli.fetch_sequence(5, layout, into_pages, into_states)
        assert np.array_equal(got[0], pages)
        kv.withdraw_sequence(5, layout, 2, registry=reg)
        with pytest.raises(kv.KvMissError):
            kv.withdraw_sequence(5, layout, 2)


def test_the_page_trio_is_the_layout_of_one_kind_with_one_page(node):
    """`publish_page` / `fetch_page` / `withdraw_page`: the ids, the
    slab's bytes, the landed bytes and the registry traffic they always
    had, and no hand-over counted."""
    addr, reg, cli = node
    layers = 61
    record = TOKENS * WIDTH * 2
    page = np.random.default_rng(3).integers(
        0, 1 << 16, (layers, TOKENS, WIDTH), dtype=np.uint16)
    before = _vars()
    with RmaBuffer(2 * page.nbytes) as slab, RmaBuffer(page.nbytes) as land:
        metas = kv.publish_page(40, page, slab, offset=page.nbytes,
                                lease_ms=LEASE, node=addr, registry=reg)
        assert [m.block_id for m in metas] == [
            kv.page_record_id(40, layer) for layer in range(layers)]
        assert [m.block_id for m in metas] == [
            (40 << 16) | (layer + 1) for layer in range(layers)]
        assert [(m.off - metas[0].off, m.length) for m in metas] == [
            (layer * record, record) for layer in range(layers)]
        assert np.array_equal(
            np.frombuffer(slab.view, np.uint16)[page.size:],
            page.reshape(-1))
        moved = _moved(before)
        assert (moved["kv_reg_many_total"], moved["kv_reg_many_records"]) \
            == (1, layers)
        landing = np.frombuffer(land.view, np.uint16).reshape(page.shape)
        assert cli.fetch_page(40, landing) is landing
        assert np.array_equal(landing, page)
        moved = _moved(before)
        assert (moved["kv_reg_many_total"], moved["kv_reg_many_records"]) \
            == (2, 2 * layers)
        assert (moved["kv_fetch_many_total"], moved["kv_fetch_total"]) == (
            1, layers)
        kv.withdraw_page(40, layers, registry=reg)
        moved = _moved(before)
        assert (moved["kv_reg_many_total"], moved["kv_reg_many_records"]) \
            == (3, 3 * layers)
        assert kv.store_count() == 0 == kv.registry_count()
        assert moved["kv_seq_total"] == 0 == moved["kv_seq_refused"]
        # A sequence of one page of the same layout is the same records.
        layout = kv.KvCacheLayout.paged(layers, record)
        kv.publish_sequence(40, layout, page[None], None, slab,
                            lease_ms=LEASE, node=addr, registry=reg)
        for layer in range(layers):
            cli.invalidate(kv.page_record_id(40, layer))
        landing[...] = 0
        cli.fetch_page(40, landing)
        assert np.array_equal(landing, page)
        kv.withdraw_page(40, layers, registry=reg)


def test_a_state_pool_is_a_pool_and_pages_move_many_at_a_time():
    """`models/kv_pool.py` for a rank of two pools: `seeded_pool`,
    `read_page` and `write_page` take a state pool as they take a page
    pool, and `read_pages` / `write_pages` move a sequence's pages, which
    lie in slots of their own, in one program with the pool donated."""
    states = kv_pool.seeded_pool(5, 3, 20, ROWS, 128)
    assert states.shape == (3, 20, ROWS, 128) and states.dtype == np.uint16
    was = np.array(states)
    snapshot = np.full((20, ROWS, 128), 0xC0DE, np.uint16)
    states = kv_pool.seeded_pool(5, 3, 20, ROWS, 128)
    states = kv_pool.write_page(states, 2, snapshot)
    assert np.array_equal(kv_pool.read_page(states, 2), snapshot)
    assert np.array_equal(kv_pool.read_page(states, 0), was[0])
    pool = kv_pool.seeded_pool(6, 9, 7, TOKENS, WIDTH)
    was = np.array(pool)
    slots = jnp.asarray([7, 0, 4])
    assert np.array_equal(kv_pool.read_pages(pool, slots), was[[7, 0, 4]])
    pool = kv_pool.seeded_pool(6, 9, 7, TOKENS, WIDTH)
    fresh = np.random.default_rng(1).integers(
        0, 1 << 16, (3, 7, TOKENS, WIDTH), dtype=np.uint16)
    new = kv_pool.write_pages(pool, slots, fresh)
    assert pool.is_deleted()                    # the donation took
    want = was.copy()
    want[[7, 0, 4]] = fresh
    assert np.array_equal(new, want)
