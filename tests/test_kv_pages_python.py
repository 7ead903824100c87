"""The KV plane at a block's width (ISSUE 27): registry calls that carry
many records in one RPC, a fetch with many records in flight on one
pipeline, and pages of a paged pool on the device published and landed
one record a layer, against the single calls and against the plain
reference (brpc_tpu/models/kv_pool_reference.py).  Small sizes, seeded;
nothing here is a measurement."""

import random

import jax
import numpy as np
import pytest

from brpc_tpu.models import kv_pool
from brpc_tpu.models.kv_pool_reference import KvDisaggReference
from brpc_tpu.rpc import Channel, RmaBuffer, RpcError, Server, kv, observe

LAYERS, TOKENS, WIDTH = 61, 4, 16          # 61 records of 128 bytes a page
RECORD = TOKENS * WIDTH * 2
LEASE = 600000
MANY_VARS = ("kv_reg_many_total", "kv_reg_many_records",
             "kv_fetch_many_total", "kv_fetch_many_records",
             "kv_register_total", "kv_lookup_total", "kv_fetch_total")


def _vars():
    dumped = observe.Vars.dump()
    return {k: dumped.get(k, 0) for k in MANY_VARS}


def _moved(before):
    return {k: v - before[k] for k, v in _vars().items()}


@pytest.fixture()
def node():
    """One in-process prefill node (store and registry), a registry
    client and a decode-side client over shm."""
    kv.reset()
    srv = Server()
    srv.enable_kv_store()
    srv.enable_kv_registry()
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    reg = kv.KvRegistryClient(Channel(addr, timeout_ms=10000),
                              owns_channel=True)
    cli = kv.KvClient(addr, use_shm=True, timeout_ms=10000)
    yield addr, reg, cli
    cli.close()
    reg.close()
    srv.stop()
    kv.reset()


def _answer(x) -> str:
    """A batch call's entry as the reference names it."""
    if isinstance(x, kv.KvMissError):
        return "miss"
    if isinstance(x, kv.KvStaleError):
        return "stale"
    if isinstance(x, kv.KvExistsError):
        return "exists"
    assert not isinstance(x, Exception), x
    return "ok"


def _meta(block_id, generation=1):
    return kv.KvBlockMeta(block_id, generation, rkey=0x42, off=0,
                          length=RECORD, node="127.0.0.1:1")


def _single(call, *args):
    try:
        call(*args)
    except kv.KvError as e:
        return _answer(e)
    return "ok"


def test_many_calls_answer_per_record_as_the_single_calls_and_the_reference(
        node):
    _addr, reg, _cli = node
    ref = KvDisaggReference(np.zeros((1, LAYERS, 1, 1), np.uint16),
                            np.zeros((1, LAYERS, 1, 1), np.uint16))
    before = _vars()
    # 61 records in one RPC; record 30 repeats record 3 (a live
    # duplicate) and record 45 offers generation 0 (never minted).
    metas = [_meta(100 + i) for i in range(LAYERS)]
    metas[30] = _meta(103)
    metas[45] = _meta(145, generation=0)
    got = reg.register_many(metas, lease_ms=LEASE)
    want = ref.register([((m.block_id, 0), m.generation) for m in metas])
    assert [_answer(x) for x in got] == want
    assert want.count("ok") == 59 and want[30] == "exists"
    assert want[45] == "stale"
    assert [x for x in got if not isinstance(x, Exception)] == [1] * 59
    # The same offers one at a time, now all against live records.
    again = [_single(reg.register, m, LEASE) for m in metas]
    assert again == ref.register(
        [((m.block_id, 0), m.generation) for m in metas])
    assert again.count("exists") == 60 and again[45] == "stale"

    ids = [100 + i for i in range(LAYERS)]
    looked = reg.lookup_many(ids)
    ref_looked = ["hit" if (i, 0) in ref.registry else "miss" for i in ids]
    assert ["hit" if isinstance(x, kv.KvBlockMeta) else _answer(x)
            for x in looked] == ref_looked
    assert ref_looked.count("miss") == 2    # 130 and 145, mid-batch
    for block_id, x in zip(ids, looked):
        if isinstance(x, kv.KvBlockMeta):
            one = reg.lookup(block_id)
            assert (x.block_id, x.generation, x.rkey, x.length, x.node) == (
                one.block_id, one.generation, one.rkey, one.length, one.node)
            assert 0 < x.lease_left_ms <= LEASE
        else:
            with pytest.raises(kv.KvMissError):
                reg.lookup(block_id)

    gone = reg.evict_many(ids)
    assert ["hit" if x == 1 else _answer(x) for x in gone] == ref.evict(
        [(i, 0) for i in ids])
    assert [_single(reg.evict, i) for i in ids] == ["miss"] * LAYERS
    assert kv.registry_count() == 0

    moved = _moved(before)
    assert moved["kv_reg_many_total"] == 3
    assert moved["kv_reg_many_records"] == 3 * LAYERS
    assert moved["kv_register_total"] == 59     # accepted, per record
    assert moved["kv_lookup_total"] == 2 * LAYERS - 2 + 2


def test_a_count_over_the_cap_is_refused_by_the_server_and_split_by_the_client(
        node):
    addr, reg, _cli = node
    raw = Channel(addr, timeout_ms=10000)
    try:
        for count, body in ((0, b""), (kv.MANY_MAX + 1, b""),
                            (2, kv._req(1))):
            with pytest.raises(RpcError, match="record count"):
                raw.call(kv.LOOKUP_MANY_METHOD,
                         kv._COUNT.pack(count) + body)
    finally:
        raw.close()
    before = _vars()
    ids = list(range(1, kv.MANY_MAX + 2))
    took = reg.register_many([_meta(i) for i in ids], lease_ms=LEASE)
    assert took == [1] * len(ids)
    assert kv.registry_count() == len(ids)
    moved = _moved(before)
    assert moved["kv_reg_many_total"] == 2
    assert moved["kv_reg_many_records"] == len(ids)
    assert reg.lookup_many([]) == []


@pytest.fixture()
def published(node):
    """61 records of 128 bytes published from one slab and registered."""
    addr, reg, cli = node
    slab = RmaBuffer(LAYERS * RECORD)
    rng = np.random.default_rng(27)
    bits = rng.integers(0, 256, LAYERS * RECORD, dtype=np.uint8)
    np.frombuffer(slab.view, np.uint8)[:] = bits
    ids = [500 + i for i in range(LAYERS)]
    metas = [kv.publish(i, slab, offset=n * RECORD, length=RECORD,
                        lease_ms=LEASE, node=addr)
             for n, i in enumerate(ids)]
    assert reg.register_many(metas, lease_ms=LEASE) == [1] * LAYERS
    yield addr, reg, cli, slab, ids, bits.reshape(LAYERS, RECORD)
    slab.free()


def test_fetch_many_lands_what_61_fetches_land(published):
    _addr, _reg, cli, _slab, ids, bits = published
    one_by_one = np.zeros((LAYERS, RECORD), np.uint8)
    for n, i in enumerate(ids):
        assert cli.fetch(i, resp_buf=one_by_one[n]) == RECORD
    before = _vars()
    together = np.zeros((LAYERS, RECORD), np.uint8)
    assert cli.fetch_many(ids, list(together)) == [RECORD] * LAYERS
    assert np.array_equal(together, one_by_one)
    assert np.array_equal(together, bits)
    moved = _moved(before)
    assert moved["kv_fetch_many_total"] == 1
    assert moved["kv_fetch_many_records"] == LAYERS
    assert moved["kv_fetch_total"] == LAYERS    # still counted per record
    assert moved["kv_reg_many_total"] == 0      # every lookup was cached
    # One pipeline for the node, as long-lived as its channel.
    assert len(cli._node_pipes) == 1
    assert cli.transports() == {published[0]: "shm_ring"}
    with pytest.raises(ValueError, match="length must match"):
        cli.fetch_many(ids, list(together)[:-1])


def test_a_withdrawn_record_fails_only_itself_and_says_which(published):
    _addr, _reg, cli, _slab, ids, bits = published
    kv.withdraw(ids[17])
    landed = np.zeros((LAYERS, RECORD), np.uint8)
    with pytest.raises(kv.KvFetchManyError) as failure:
        cli.fetch_many(ids, list(landed))
    assert list(failure.value.failed) == [ids[17]]
    assert isinstance(failure.value.failed[ids[17]], kv.KvStaleError)
    assert str(ids[17]) in str(failure.value)
    rest = [n for n in range(LAYERS) if n != 17]
    assert np.array_equal(landed[rest], bits[rest])
    assert not landed[17].any()       # never older bytes
    # A record the registry never had fails the same way, as a miss.
    with pytest.raises(kv.KvFetchManyError) as failure:
        cli.fetch_many([ids[0], 999], [landed[0], landed[1]])
    assert isinstance(failure.value.failed[999], kv.KvMissError)


def test_a_stale_generation_re_resolves_that_record_once(published):
    addr, reg, cli, slab, ids, bits = published
    landed = np.zeros((LAYERS, RECORD), np.uint8)
    cli.fetch_many(ids, list(landed))           # the cache holds gen 1
    view = np.frombuffer(slab.view, np.uint8)
    for n in (5, 40):                           # re-published: gen 2
        kv.withdraw(ids[n])
        view[n * RECORD:(n + 1) * RECORD] ^= 0xFF
        meta = kv.publish(ids[n], slab, offset=n * RECORD, length=RECORD,
                          lease_ms=LEASE, node=addr)
        assert meta.generation == 2
        assert reg.register(meta, lease_ms=LEASE) == 2
    before = _vars()
    invalidated = cli.invalidations
    assert cli.fetch_many(ids, list(landed)) == [RECORD] * LAYERS
    want = bits.copy()
    want[[5, 40]] ^= 0xFF
    assert np.array_equal(landed, want)
    assert cli.invalidations == invalidated + 2
    moved = _moved(before)
    assert moved["kv_reg_many_total"] == 1      # one re-lookup, 2 records
    assert moved["kv_reg_many_records"] == 2
    assert moved["kv_fetch_total"] == LAYERS    # 59 + the 2 retried
    assert cli.lookup(ids[5]).generation == 2


def test_write_page_takes_its_donation_and_read_page_reads_it_back():
    pool = kv_pool.seeded_pool(3, 5, LAYERS, TOKENS, WIDTH)
    assert pool.shape == (5, LAYERS, TOKENS, WIDTH)
    assert pool.dtype == np.uint16
    again = kv_pool.seeded_pool(3, 5, LAYERS, TOKENS, WIDTH)
    other = kv_pool.seeded_pool(4, 5, LAYERS, TOKENS, WIDTH)
    assert np.array_equal(pool, again) and not np.array_equal(pool, other)
    assert len({np.asarray(pool[s]).tobytes() for s in range(5)}) == 5
    # A pool no numpy view shares (on the CPU such a view is the buffer
    # itself, and a shared buffer cannot be given away).
    was = np.array(pool)
    pool = kv_pool.seeded_pool(3, 5, LAYERS, TOKENS, WIDTH)
    page = np.full((LAYERS, TOKENS, WIDTH), 0xBEEF, np.uint16)
    new = kv_pool.write_page(pool, 3, page)
    assert pool.is_deleted()                    # the donation took
    want = was.copy()
    want[3] = page
    assert np.array_equal(new, want)
    assert np.array_equal(kv_pool.read_page(new, 3), page)
    assert not new.is_deleted()                 # a read donates nothing


def _system_answers(call, *args):
    got = call(*args)
    return ["hit" if isinstance(x, (int, kv.KvBlockMeta)) else _answer(x)
            for x in got]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_pages_through_the_kv_plane_match_the_reference(node, seed):
    """A seeded sequence of produce, publish, register, lookup, fetch,
    write, evict and withdraw over a few blocks, legal and not: each
    answer and, at the end, both pools, against the reference."""
    addr, reg, cli = node
    pages = 6
    prefill = kv_pool.seeded_pool(seed, pages, LAYERS, TOKENS, WIDTH)
    decode = kv_pool.seeded_pool(seed + 100, pages, LAYERS, TOKENS, WIDTH)
    ref = KvDisaggReference(np.asarray(prefill), np.asarray(decode))
    slab = RmaBuffer(4 * LAYERS * RECORD)
    land = RmaBuffer(LAYERS * RECORD)
    landing = np.frombuffer(land.view, np.uint16).reshape(
        LAYERS, TOKENS, WIDTH)
    rng = random.Random(seed)
    blocks = [1, 2, 3, 4]
    metas: dict = {}         # block -> the metas of its last publish
    fetched = None
    ops = 0
    try:
        for step in range(48):
            op = rng.choice(["produce", "publish", "publish", "register",
                             "lookup", "fetch", "fetch", "write", "evict",
                             "withdraw"])
            block, slot = rng.choice(blocks), rng.randrange(pages)
            records = ref.records(block)
            ids = [kv.page_record_id(block, layer)
                   for layer in range(LAYERS)]
            if op == "produce":
                page = np.random.default_rng(step).integers(
                    0, 1 << 16, (LAYERS, TOKENS, WIDTH), dtype=np.uint16)
                prefill = kv_pool.write_page(prefill, slot, page)
                ref.produce(slot, page)
            elif op == "publish":
                try:
                    metas[block] = kv.publish_page(
                        block, kv_pool.read_page(prefill, slot), slab,
                        offset=(block - 1) * LAYERS * RECORD,
                        lease_ms=LEASE, node=addr, registry=reg)
                    got = "ok"
                except kv.KvExistsError:
                    got = "exists"
                assert got == ref.publish(block, slot), (step, op)
            elif op == "register" and block in metas:
                got = [_answer(x) for x in reg.register_many(
                    metas[block], lease_ms=LEASE)]
                assert got == ref.register(
                    [(r, m.generation)
                     for r, m in zip(records, metas[block])]), (step, op)
            elif op == "lookup":
                assert _system_answers(cli.lookup_many, ids) == ref.lookup(
                    records), (step, op)
            elif op == "fetch":
                want, answers = ref.fetch(block)
                try:
                    fetched = cli.fetch_page(block, landing).copy()
                    assert np.array_equal(fetched, want), (step, op)
                except kv.KvFetchManyError as e:
                    got = {rid: _answer(x) for rid, x in e.failed.items()}
                    assert got == {rid: a for rid, a in zip(ids, answers)
                                   if a != "hit"}, (step, op)
            elif op == "write" and fetched is not None:
                decode = kv_pool.write_page(decode, slot,
                                            jax.device_put(fetched))
                ref.write(slot, fetched)
            elif op == "evict":
                got = ["hit" if isinstance(x, int) else _answer(x)
                       for x in reg.evict_many(ids)]
                assert got == ref.evict(records), (step, op)
            elif op == "withdraw":
                try:
                    kv.withdraw_page(block, LAYERS)
                    got = ["ok"] * LAYERS
                except kv.KvMissError:
                    got = ["miss"] * LAYERS
                assert got == ref.withdraw(block), (step, op)
            else:
                continue
            ops += 1
        assert ops >= 32
        assert np.array_equal(np.asarray(prefill), np.asarray(ref.prefill))
        assert np.array_equal(np.asarray(decode), np.asarray(ref.decode))
    finally:
        slab.free()
        land.free()


def test_publish_page_refuses_a_page_the_slab_cannot_hold(node):
    addr, reg, _cli = node
    page = np.zeros((LAYERS, TOKENS, WIDTH), np.uint16)
    with RmaBuffer(LAYERS * RECORD) as slab:
        with pytest.raises(ValueError, match="does not fit the slab"):
            kv.publish_page(9, page, slab, offset=RECORD, node=addr)
        assert kv.store_count() == 0
        metas = kv.publish_page(9, page, slab, lease_ms=LEASE, node=addr,
                                registry=reg)
        assert [m.block_id for m in metas] == [
            kv.page_record_id(9, layer) for layer in range(LAYERS)]
        assert len(set(m.block_id for m in metas)) == LAYERS
        assert kv.store_count() == LAYERS == kv.registry_count()
        kv.withdraw_page(9, LAYERS, registry=reg)
        assert kv.store_count() == 0 == kv.registry_count()
    with pytest.raises(ValueError, match="no record id"):
        kv.page_record_id(1, 1 << 16)
