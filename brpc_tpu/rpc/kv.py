"""Paged KV-block registry client — block-addressed KV-cache transfer.

The Python surface of cpp/net/kvstore.h (fabric-lib's abstraction,
arXiv 2510.27656): KV blocks are addressed by BLOCK ID through a
registry record {node, rkey, offset, len, generation}, never by
connection.  A prefill node `publish()`es blocks out of an `RmaBuffer`
(the store serves their bytes zero-copy from the registered pages) and
registers them; a decode node's `KvClient` looks blocks up (cached,
generation-checked), fetches them from the owning node, and can land
them ONE-SIDED in its own `RmaBuffer` via the PR 10 direct-landing path
(`fetch(..., resp_buf=...)`) — zero receiver-side copies over shm/ici,
transparent striped-copy degradation over TCP.

Cache-coherence contract: a cached lookup is used until a fetch proves
it stale — the owning node validates generation AND lease at serve time
and answers kv-stale (KvStaleError) on any mismatch, which invalidates
the cached record, re-resolves it through the registry once, and
retries.  A lease that expires while a fetch is in flight therefore
never admits stale bytes; a chunk fault fails the call whole (the
landing buffer is never partially complete).

Typical prefill side::

    srv = Server(); srv.enable_kv_store(); srv.enable_kv_registry()
    srv.start(0)
    pages = RmaBuffer(64 << 20)
    ...fill pages.view...
    meta = kv.publish(1001, pages, length=4 << 20,
                      node=f"127.0.0.1:{srv.port}")
    reg = kv.KvRegistryClient(Channel(f"127.0.0.1:{srv.port}"))
    reg.register(meta)

Typical decode side::

    cli = kv.KvClient(registry_addr, use_shm=True)
    land = RmaBuffer(4 << 20)
    n = cli.fetch(1001, resp_buf=land.view)   # one-sided landing

A paged latent cache (brpc_tpu/models/kv_pool.py) moves one page at a
time, one record a layer, a block's records in one registry RPC and its
fetches in flight together.  Prefill side, the page still in HBM::

    slab = RmaBuffer(page.nbytes)
    kv.publish_page(7, kv_pool.read_page(prefill_pool, slot), slab,
                    node=addr, registry=reg)

Decode side, into its own pool in HBM::

    land = RmaBuffer(page_bytes)
    host = np.frombuffer(land.view, np.uint16).reshape(
        layers, page_tokens, width)
    cli.fetch_page(7, host)                   # 61 Kv.Fetch in flight
    decode_pool = kv_pool.write_page(decode_pool, slot,
                                     jax.device_put(host))
    kv.withdraw_page(7, layers, registry=reg)  # prefill side, when done

A cache of more than one kind (latent attention beside linear attention)
moves a sequence at a time: a `KvCacheLayout` says per layer whether it
is paged (a record a page) or holds one snapshot a sequence (a recurrent
state), and `publish_sequence` / `KvClient.fetch_sequence` /
`withdraw_sequence` hand the pages and the snapshots over as one unit,
one registry RPC each and every record's fetch in one round.  Only the
sequence's id and its length in pages cross between the ranks; the
snapshots asked for are those taken at that boundary::

    layout = kv.KvCacheLayout(kinds, record_bytes)     # on both ranks
    kv.publish_sequence(31, layout, pages, states, slab,
                        node=addr, registry=reg)       # prefill side
    pages, states = cli.fetch_sequence(31, layout, pages_landing,
                                       states_landing)  # decode side
    kv.withdraw_sequence(31, layout, n_pages, registry=reg)

The page calls above are the layout of one paged kind with one page.

The other half of the plane is a content-addressed PREFIX CACHE: a block
is one page of a prompt's cache, named by a chain key (the token ids of
the whole prefix through that page, `prefix_chain`) and by the content
hash of its bytes and token span.  Nothing is positional, a block is
asked for as often as prompts share it, and the node's two-tier store
keeps it under two budgets: hot blocks in registered pages
(`trpc_kv_prefix_hot_bytes`; past it the least recently touched is
demoted to the heap, never dropped) and everything under
`trpc_kv_store_bytes` (past it expired, then heap, then hot blocks are
dropped, and a dropped block answers kv-stale).  A touch, a fetch's or
the publish of content the store already holds, leaves its block hot, so
the two tiers are one order by last touch.  A rank that admits the
next turn of a conversation::

    groups = cli.match_prefix(tokens)            # one KvReg.Match
    landed = cli.fetch_prefix_blocks(groups, landing=rows, window=16)
    pool = kv_pool.write_pages(pool, slots[:len(landed)],
                               jax.device_put(rows[:len(landed)]))
    # ...prefill the pages from len(landed) on, then offer them:
    kv.publish_prefix_run(keys[len(landed):], len(landed),
                          zerocopy.host_view(new_pages)[0], token_spans,
                          node=addr, registry=reg)   # one PutPrefixMany

`fetch_prefix_blocks` rides the node channel's pipeline as `Kv.Fetch`
does (a window of blocks in flight, each over `trpc_stripe_threshold`
through the connection's one-sided window) and ends the run at the first
block no replica serves; `publish_prefix_run` hashes the run's pages four
at a time where their bytes lie, and the store takes each there, with no
copy, when they lie in the landing block its device-to-host transfer
wrote (`prefix_publish`), else copies it once.
"""

from __future__ import annotations

import ctypes
import dataclasses
import struct

import numpy as np

from brpc_tpu.rpc import zerocopy
from brpc_tpu.rpc._lib import load_library
from brpc_tpu.rpc.client import Channel, RpcError

# Wire form shared by every Kv RPC — MUST mirror cpp/net/kvstore.h
# KvWire (kv-wire marker: fixed little-endian, 112 bytes).
_WIRE = struct.Struct("<QQQQQq64s")
assert _WIRE.size == 112

# Prefix-cache wire form — MUST mirror cpp/net/kvstore.h KvPrefixWire
# (kv-wire marker: fixed little-endian, 144 bytes): key hi/lo, hash
# hi/lo, generation, rkey, off, len, lease_ms, depth, flags, node.
_PREFIX_WIRE = struct.Struct("<QQQQQQQQqII64s")
assert _PREFIX_WIRE.size == 144

# Batch forms — MUST mirror cpp/net/kvstore.h KvManyGen / KvManyRecord
# (kv-wire marker): a u64 count then that many KvWire in; the count,
# then one entry per request entry out (status, and the generation or
# the record).  MANY_MAX is kKvManyMax.
_COUNT = struct.Struct("<Q")
_MANY_GEN = struct.Struct("<qQ")
_MANY_RECORD = struct.Struct("<q112s")
assert _MANY_GEN.size == 16 and _MANY_RECORD.size == 120
MANY_MAX = 4096

FETCH_METHOD = "Kv.Fetch"
REGISTER_METHOD = "KvReg.Register"
LOOKUP_METHOD = "KvReg.Lookup"
EVICT_METHOD = "KvReg.Evict"
RENEW_METHOD = "KvReg.Renew"
REGISTER_MANY_METHOD = "KvReg.RegisterMany"
LOOKUP_MANY_METHOD = "KvReg.LookupMany"
EVICT_MANY_METHOD = "KvReg.EvictMany"
PREFIX_PUT_METHOD = "KvReg.PutPrefix"
PREFIX_PUT_MANY_METHOD = "KvReg.PutPrefixMany"
PREFIX_MATCH_METHOD = "KvReg.Match"
PREFIX_FETCH_METHOD = "Kv.FetchPrefix"


class KvError(RpcError):
    """Base of the kv error family (codes 2101..2103)."""


class KvMissError(KvError):
    """Block unknown (never registered, or lease expired and pruned)."""


class KvStaleError(KvError):
    """The caller's record is outdated — generation bumped, lease
    lapsed, or block evicted.  Cached lookups must invalidate."""


class KvExistsError(KvError):
    """Double-register of a live block (ownership is exclusive while
    the lease holds)."""


class KvFetchManyError(KvError):
    """A multi-record fetch in which some records did not land; the
    call failed as a whole.  `failed` maps each such block id to its
    error (KvMissError, KvStaleError or a transport RpcError); the code
    is the first one's."""

    def __init__(self, failed: dict):
        first = next(iter(failed.values()))
        super().__init__(
            first.code, f"{len(failed)} record(s) did not land: "
            + ", ".join(f"{bid}: {e.text}" for bid, e in failed.items()))
        self.failed = failed


def _codes() -> tuple[int, int, int]:
    lib = load_library()
    miss = ctypes.c_int()
    stale = ctypes.c_int()
    exists = ctypes.c_int()
    lib.trpc_kv_codes(ctypes.byref(miss), ctypes.byref(stale),
                      ctypes.byref(exists))
    return miss.value, stale.value, exists.value


def _kv_error(e: RpcError) -> RpcError:
    miss, stale, exists = _codes()
    cls = {miss: KvMissError, stale: KvStaleError,
           exists: KvExistsError}.get(e.code)
    return cls(e.code, e.text) if cls is not None else e


def _entry_error(status: int, what: str) -> RpcError:
    """One entry's status of a batch answer, as the single call's error."""
    miss, stale, exists = _codes()
    why = {miss: "kv-miss", stale: "kv-stale",
           exists: "kv-exists"}.get(status, "kv-error")
    return _kv_error(RpcError(status, f"{why}: {what}"))


@dataclasses.dataclass
class KvBlockMeta:
    """One registry record: where block_id's bytes live right now."""

    block_id: int
    generation: int
    rkey: int
    off: int
    length: int
    node: str = ""
    lease_left_ms: int = 0

    def pack(self, lease_ms: int = 0) -> bytes:
        return _WIRE.pack(self.block_id, self.generation, self.rkey,
                          self.off, self.length, lease_ms,
                          self.node.encode()[:63])

    @classmethod
    def unpack(cls, data: bytes) -> "KvBlockMeta":
        bid, gen, rkey, off, length, lease, node = _WIRE.unpack_from(data)
        return cls(bid, gen, rkey, off, length,
                   node.split(b"\0", 1)[0].decode(errors="replace"), lease)


def _req(block_id: int, generation: int = 0, lease_ms: int = 0) -> bytes:
    return _WIRE.pack(block_id, generation, 0, 0, 0, lease_ms, b"")


def publish(block_id: int, buffer, offset: int = 0, length: int | None = None,
            lease_ms: int = 0, node: str = "",
            min_generation: int = 0) -> KvBlockMeta:
    """Publishes `length` bytes at `offset` of an RmaBuffer into this
    process's block store (native, zero-copy serving) and returns the
    registry-ready record.  lease_ms <= 0 uses the trpc_kv_lease_ms
    default.  Raises KvExistsError while the block is live.
    min_generation floors the minted generation — a hot-restart
    successor (fresh pid) passes the predecessor's last registry
    generation + 1 so its takeover re-publish outranks every cached
    record (drain flow, cpp/net/naming.h)."""
    base = buffer.address if hasattr(buffer, "address") else \
        ctypes.addressof((ctypes.c_char * 0).from_buffer(buffer))
    size = buffer.nbytes if hasattr(buffer, "nbytes") else len(buffer)
    if length is None:
        length = size - offset
    if offset < 0 or length <= 0 or offset + length > size:
        raise ValueError(f"bad block range: off={offset} len={length} "
                         f"of {size}")
    return _publish_at(block_id, base + offset, length, lease_ms, node,
                       min_generation)


def _publish_at(block_id: int, address: int, length: int, lease_ms: int,
                node: str, min_generation: int = 0) -> KvBlockMeta:
    """`publish` of the `length` bytes at `address`, which must lie in
    registered memory."""
    lib = load_library()
    gen = ctypes.c_uint64()
    rkey = ctypes.c_uint64()
    off = ctypes.c_uint64()
    # Plain ints and the out-parameters themselves: the declared argtypes
    # convert them, which is a third cheaper a record than wrapping each
    # (61 or 76 records a hand-over, on the thread that binds the KV cells).
    rc = lib.trpc_kv_publish_ex(address, length, block_id, lease_ms,
                                min_generation, gen, rkey, off)
    if rc != 0:
        miss, stale, exists = _codes()
        if rc == exists:
            raise KvExistsError(rc, f"block {block_id} is live")
        raise MemoryError(
            f"kv publish failed (rc={rc}): the bytes must lie inside an "
            "RmaBuffer and fit trpc_kv_store_bytes")
    return KvBlockMeta(block_id, gen.value, rkey.value, off.value, length,
                       node)


def withdraw(block_id: int) -> None:
    """Evicts a local block (its generation tombstones, so stale fetches
    stay detectable).  Raises KvMissError if unknown."""
    rc = load_library().trpc_kv_withdraw(ctypes.c_uint64(block_id))
    if rc != 0:
        raise KvMissError(rc, f"block {block_id} not in the local store")


def renew(block_id: int, lease_ms: int = 0) -> None:
    """Extends a local block's lease."""
    rc = load_library().trpc_kv_renew(ctypes.c_uint64(block_id),
                                      ctypes.c_int64(lease_ms))
    if rc != 0:
        raise KvMissError(rc, f"block {block_id} not in the local store")


def store_count() -> int:
    return int(load_library().trpc_kv_store_count())


def store_bytes_used() -> int:
    return int(load_library().trpc_kv_store_bytes_used())


def registry_count() -> int:
    return int(load_library().trpc_kv_registry_count())


def reset() -> None:
    """Test support: drops every local block and registry record."""
    load_library().trpc_kv_reset()


# ---- a cache of layers: paged records and per-sequence snapshots ----------

_MAX_LAYERS = 1 << 16
_MAX_SEQUENCE_PAGES = 1 << 15
PAGED = "paged"
SNAPSHOT = "snapshot"


def page_record_id(block_id: int, layer: int) -> int:
    """The store's id of layer `layer`'s record of page `block_id`: both
    sides derive it, nothing but the page's id crosses between them."""
    if not (0 <= layer < _MAX_LAYERS - 1 and 0 <= block_id < 1 << 47):
        raise ValueError(f"no record id for page {block_id} layer {layer}")
    return (block_id << 16) | (layer + 1)


def sequence_record_id(seq_id: int, layer: int, number: int) -> int:
    """The store's id of one record of sequence `seq_id`.  `number` is
    the page's number in the sequence for a paged layer, and for a
    snapshot layer the boundary the state was taken at, in pages: the
    boundary is part of the id, so a snapshot of another boundary is
    another record (kv-miss), never older bytes under the same id.  A
    layer is of one kind, so the two never meet.  Number 0 of sequence
    `s` is `page_record_id(s, layer)`: a lone page is a sequence of one."""
    if not (0 <= number < _MAX_SEQUENCE_PAGES
            and (number == 0 or 0 <= seq_id < 1 << 32)):
        raise ValueError(
            f"no record id for sequence {seq_id} layer {layer} at {number}")
    return page_record_id((number << 32) | seq_id, layer)


@dataclasses.dataclass(frozen=True)
class KvCacheLayout:
    """What a model's cache is made of, layer by layer.  A `paged` layer
    holds one record of `record_bytes[layer]` a page of tokens (keys and
    values, or MLA's latent); a `snapshot` layer holds one record a
    sequence whatever its length (a linear-attention layer's recurrent
    state), which is only worth anything with the pages of the boundary
    it was taken at.  Both ranks hold the same layout; a sequence's id
    and its length in pages are all that crosses between them."""

    kinds: tuple[str, ...]
    record_bytes: tuple[int, ...]

    def __post_init__(self):
        if (len(self.kinds) != len(self.record_bytes)
                or not 0 < len(self.kinds) < _MAX_LAYERS
                or any(k not in (PAGED, SNAPSHOT) for k in self.kinds)
                or any(n <= 0 for n in self.record_bytes)):
            raise ValueError(f"not a cache layout: {self.kinds} of "
                             f"{self.record_bytes} bytes")

    @classmethod
    def paged(cls, layers: int, record_bytes: int) -> "KvCacheLayout":
        return cls((PAGED,) * layers, (record_bytes,) * layers)

    def layers_of(self, kind: str) -> list[int]:
        return [l for l, k in enumerate(self.kinds) if k == kind]

    def records(self, seq_id: int, pages: int) -> tuple[list, list]:
        """(paged, snapshot): the (record id, bytes) of a sequence of
        `pages` pages, in the order its bytes lie in a slab or a landing
        area: page by page each paged layer's record, then each snapshot
        layer's at the boundary `pages`."""
        if pages < 1:
            raise ValueError(f"a sequence of {pages} pages has no records")
        paged_layers = self.layers_of(PAGED)
        paged = [(sequence_record_id(seq_id, l, page), self.record_bytes[l])
                 for page in range(pages) for l in paged_layers]
        snapshot = [(sequence_record_id(seq_id, l, pages),
                     self.record_bytes[l])
                    for l in self.layers_of(SNAPSHOT)]
        return paged, snapshot

    def record_ids(self, seq_id: int, pages: int) -> list[int]:
        paged, snapshot = self.records(seq_id, pages)
        return [rid for rid, _ in paged + snapshot]

    def sequence_bytes(self, pages: int) -> int:
        return sum(n * (pages if k == PAGED else 1)
                   for k, n in zip(self.kinds, self.record_bytes))


def _host_flat(array) -> np.ndarray:
    """The bytes of a device array, or of the view `zerocopy.host_view`
    made of one when its transfer was started ahead: there already if
    `zerocopy`'s waiter has seen it through meanwhile, else waited for."""
    return (array.resolve() if isinstance(array, zerocopy.PendingView)
            else zerocopy.host_bytes(array)[0])


def _cut(area, records) -> list:
    """A landing area (a writable C-contiguous numpy array) cut into the
    place of each of `records` ((id, bytes)), which lie in it end to
    end and fill it."""
    total = sum(n for _, n in records)
    if not area.flags.c_contiguous or area.nbytes != total:
        raise ValueError(
            f"a landing area of {area.nbytes} bytes for {len(records)} "
            f"records of {total} (C-contiguous: "
            f"{area.flags.c_contiguous})")
    flat = area.reshape(-1).view(np.uint8)
    bufs, at = [], 0
    for _, nbytes in records:
        bufs.append(flat[at:at + nbytes])
        at += nbytes
    return bufs


def _publish_records(groups, slab, offset, lease_ms, node,
                     registry) -> list[KvBlockMeta]:
    """Publishes the records of `groups`, each (records as (id, bytes),
    the flat host array whose bytes they are, end to end), in order, and
    with a `registry` registers them all in one `register_many`.

    Where a group is published from is chosen by what can be observed of
    its source.  Bytes that lie in a landing block of the host pool
    (`trpc_host_pool_holds`: where a `PendingView`'s transfer of 1 MB or
    more landed them, registered memory since PR 34) are published where
    they lie, each record at its offset in the block, and nothing is
    copied: the store co-owns the block from then on, and the pool hands
    it to no other transfer until the last record published from it has
    been withdrawn, evicted or replaced and no response serves its bytes
    any more, whatever the caller does with the view and the array
    meanwhile (cpp/capi/hostpool_capi.cc).  Any other source (a numpy
    array of the caller's, a dlpack import on the CPU) is copied into
    `slab` (an RmaBuffer), at the place it would have from `offset` on
    with every group end to end, and published from there.  Either way a
    published record's bytes are not to be written until it is gone.
    One call counts its bytes once, in `kv_publish_in_place_bytes` and
    `kv_publish_copy_bytes`."""
    sizes = [sum(n for _, n in records) for records, _ in groups]
    total = sum(sizes)
    if (any(size != flat.nbytes for size, (_, flat) in zip(sizes, groups))
            or offset < 0 or offset + total > slab.nbytes):
        raise ValueError(
            f"{sum(len(records) for records, _ in groups)} records of "
            f"{total} bytes from {sum(flat.nbytes for _, flat in groups)} "
            f"bytes at {offset}: that does not fit the slab "
            f"({slab.nbytes} bytes)")
    lib = load_library()
    served_from, copies, at = [], [], offset
    for _, flat in groups:
        address = flat.ctypes.data
        if not lib.trpc_host_pool_holds(address, flat.nbytes):
            address = slab.address + at
            copies.append((at, flat))
        served_from.append(address)
        at += flat.nbytes
    # Published first, copied second: a record that is live
    # (KvExistsError) keeps its bytes and its block, and nobody can look
    # the records up before they are registered below.
    metas: list[KvBlockMeta] = []
    try:
        for (records, _), address in zip(groups, served_from):
            for record_id, nbytes in records:
                metas.append(_publish_at(record_id, address, nbytes,
                                         lease_ms, node))
                address += nbytes
    except Exception:
        for meta in metas:
            withdraw(meta.block_id)
        raise
    copied = 0
    for at, flat in copies:
        np.frombuffer(slab.view, dtype=np.uint8)[at:at + flat.nbytes] = flat
        copied += flat.nbytes
    lib.trpc_kv_note_publish(total - copied, copied)
    if registry is not None:
        for answer in registry.register_many(metas, lease_ms=lease_ms):
            if isinstance(answer, RpcError):
                raise answer
    return metas


def publish_sequence(seq_id: int, layout: KvCacheLayout, pages, states,
                     slab, offset: int = 0, lease_ms: int = 0,
                     node: str = "",
                     registry: "KvRegistryClient | None" = None
                     ) -> list[KvBlockMeta]:
    """Publishes a sequence's cache for a hand-over to another rank:
    `pages[p, i]` as the record of page `p` of the layout's `i`-th paged
    layer, `states[j]` as the snapshot of its `j`-th snapshot layer,
    taken at the boundary `pages.shape[0]` (`states` is None where the
    layout has no such layer; where the layers' records differ in size
    an array's bytes are its records end to end, in that order, and
    only `pages.shape[0]` is read of its shape).  Both are device arrays
    or numpy arrays, or the views `zerocopy.host_view` made of them when
    their transfers were started ahead (a view of 1 MB or more is waited
    for by `zerocopy`'s own thread from the moment it is made, and a
    16-bit array crosses as flat words, so a caller that comes back a
    cycle later finds the bytes there): the bytes come to the host
    through `zerocopy.host_view`, so a transfer of 1 MB or more lands
    them in a block of the host pool, and from there each record is
    `publish`ed where it lies, with no copy on the host; the pool keeps
    that block from every other transfer until the sequence is withdrawn
    (or its records are evicted or replaced), whatever becomes of the
    view and the array.  A source that is no such block (a numpy array,
    a host-visible device array, a transfer under 1 MB) is copied into
    `slab` (an RmaBuffer) from `offset` on, at its place among the
    `layout.sequence_bytes` bytes, and its records are published from
    there: those slab bytes belong to the store until the sequence is
    withdrawn (`withdraw_sequence`).  With a `registry` the records of
    both kinds are registered in one `register_many`; a record it
    refuses raises its error.  A sequence with a live record is refused
    whole (KvExistsError) and the live records keep their bytes."""
    paged, snapshot = layout.records(seq_id, pages.shape[0])
    if states is None:
        groups = [(paged + snapshot, _host_flat(pages))]
    else:
        groups = [(paged, _host_flat(pages)),
                  (snapshot, _host_flat(states))]
    return _publish_records(groups, slab, offset, lease_ms, node, registry)


def withdraw_sequence(seq_id: int, layout: KvCacheLayout, pages: int,
                      registry: "KvRegistryClient | None" = None) -> None:
    """Takes a published sequence of `pages` pages back: its records of
    both kinds leave the registry (one `evict_many`; a record already
    gone there is no error) and the local store, after which its slab
    bytes may be used again and the landing blocks it was published
    from go back to the host pool once their arrays are dropped.  Raises
    KvMissError if the store did not hold a record."""
    ids = layout.record_ids(seq_id, pages)
    if registry is not None:
        registry.evict_many(ids)
    for record_id in ids:
        withdraw(record_id)


def publish_page(block_id: int, page, slab, offset: int = 0,
                 lease_ms: int = 0, node: str = "",
                 registry: "KvRegistryClient | None" = None
                 ) -> list[KvBlockMeta]:
    """Publishes one page of a paged pool, `page[layer]` as the record
    `page_record_id(block_id, layer)`: `publish_sequence` for a layout
    of `page.shape[0]` paged layers of equal records and a sequence of
    this one page, so out of the block the page's transfer landed in
    where there is one, and through `slab` otherwise."""
    layers = page.shape[0]
    flat = _host_flat(page)
    if flat.nbytes % layers:
        raise ValueError(f"a page of {flat.nbytes} bytes is not {layers} "
                         "layers of equal records")
    layout = KvCacheLayout.paged(layers, flat.nbytes // layers)
    return _publish_records([(layout.records(block_id, 1)[0], flat)], slab,
                            offset, lease_ms, node, registry)


def withdraw_page(block_id: int, layers: int,
                  registry: "KvRegistryClient | None" = None) -> None:
    """`withdraw_sequence` for a page that `publish_page` published."""
    withdraw_sequence(block_id, KvCacheLayout.paged(layers, 1), 1, registry)


# ---- content-addressed prefix cache (ISSUE 17) ---------------------------


@dataclasses.dataclass
class KvPrefixMeta:
    """One prefix-block replica record: chain key (where in the trie),
    content hash (what bytes), and where this replica lives."""

    key_hi: int
    key_lo: int
    hash_hi: int
    hash_lo: int
    generation: int
    rkey: int = 0
    off: int = 0
    length: int = 0
    depth: int = 0
    node: str = ""
    lease_left_ms: int = 0
    flags: int = 0  # bit 0: replica currently cold (tier telemetry)

    @property
    def key(self) -> tuple[int, int]:
        return self.key_hi, self.key_lo

    @property
    def hash(self) -> tuple[int, int]:
        return self.hash_hi, self.hash_lo

    def pack(self, lease_ms: int = 0) -> bytes:
        return _PREFIX_WIRE.pack(self.key_hi, self.key_lo, self.hash_hi,
                                 self.hash_lo, self.generation, self.rkey,
                                 self.off, self.length, lease_ms,
                                 self.depth, self.flags,
                                 self.node.encode()[:63])

    @classmethod
    def unpack(cls, data: bytes, offset: int = 0) -> "KvPrefixMeta":
        (khi, klo, hhi, hlo, gen, rkey, off, length, lease, depth, flags,
         node) = _PREFIX_WIRE.unpack_from(data, offset)
        return cls(khi, klo, hhi, hlo, gen, rkey, off, length, depth,
                   node.split(b"\0", 1)[0].decode(errors="replace"),
                   lease, flags)


def _token_array(tokens):
    toks = list(tokens)
    return (ctypes.c_uint64 * max(len(toks), 1))(*toks), len(toks)


def _byte_view(data) -> np.ndarray:
    """The bytes of `data` where they lie, as a flat uint8 array: a
    `zerocopy.PendingView` is waited for, anything else is read through
    the buffer protocol (C-contiguous), nothing copied."""
    if isinstance(data, zerocopy.PendingView):
        data = data.resolve()
    return np.frombuffer(data, dtype=np.uint8)


def content_hash(data, tokens=()) -> tuple[int, int]:
    """128-bit content hash of (block bytes, token-id span) — identical
    inputs hash identically in every process (the fleet dedup key)."""
    lib = load_library()
    flat = _byte_view(data)
    tok_arr, ntok = _token_array(tokens)
    hi = ctypes.c_uint64()
    lo = ctypes.c_uint64()
    lib.trpc_kv_content_hash(
        ctypes.c_void_p(flat.ctypes.data), ctypes.c_size_t(flat.nbytes),
        tok_arr, ctypes.c_size_t(ntok), ctypes.byref(hi), ctypes.byref(lo))
    return hi.value, lo.value


def prefix_chain(tokens, block_tokens: int = 0) -> list[tuple[int, int]]:
    """Chain keys for a token-id sequence: key_i names the WHOLE prefix
    through block i, so longest-prefix match is a walk until first miss.
    Only FULL block_tokens-sized blocks produce keys (the partial tail is
    never cacheable).  block_tokens <= 0 uses trpc_kv_prefix_block_tokens
    — every node must agree on it for keys to dedup."""
    lib = load_library()
    tok_arr, ntok = _token_array(tokens)
    if ntok == 0:
        return []
    keys = (ctypes.c_uint64 * (2 * ntok))()
    wrote = lib.trpc_kv_prefix_chain(tok_arr, ctypes.c_size_t(ntok),
                                     ctypes.c_int64(block_tokens), keys,
                                     ctypes.c_size_t(ntok))
    return [(keys[2 * i], keys[2 * i + 1]) for i in range(int(wrote))]


def prefix_publish(key: tuple[int, int], depth: int, data, tokens,
                   lease_ms: int = 0, node: str = "",
                   min_generation: int = 0) -> tuple[KvPrefixMeta, bool]:
    """Publishes one prefix block into the local two-tier store under its
    content hash.  `data` is any C-contiguous buffer (bytes, a numpy
    array) or a `zerocopy.PendingView`; the hash reads the bytes where
    they lie.  Where the block's bytes then live is chosen by what can be
    observed of their source, as `_publish_records` chooses: bytes in a
    landing block of the host pool (`trpc_host_pool_holds`: where a
    view's transfer of 1 MB or more landed them) are TAKEN where they
    lie, nothing copied, and the store co-owns the block until the prefix
    block is demoted to the heap tier or dropped, whatever becomes of the
    view and the array (the pool hands the block to no other transfer
    meanwhile); any other source (no RmaBuffer needed) is copied once
    into store-owned pages.  Counted in `kv_prefix_publish_in_place_bytes`
    / `kv_prefix_publish_copy_bytes`.  Returns (meta, fresh): fresh=False
    is the cache-hit path — identical content was already live, the lease
    renewed, and NO bytes were admitted (the caller's
    bytes-not-recomputed accounting); a block that was in the heap tier
    is hot again, on these bytes where they lie or on one copy of them
    (`kv_prefix_renew_promote`)."""
    lib = load_library()
    flat = _byte_view(data)
    if not flat.nbytes:
        raise ValueError("empty prefix block")
    tok_arr, ntok = _token_array(tokens)
    hash_hi = ctypes.c_uint64()
    hash_lo = ctypes.c_uint64()
    gen = ctypes.c_uint64()
    rkey = ctypes.c_uint64()
    off = ctypes.c_uint64()
    address = flat.ctypes.data
    rc = lib.trpc_kv_prefix_publish_at(
        key[0], key[1], depth, address, flat.nbytes, tok_arr, ntok,
        lease_ms, min_generation,
        lib.trpc_host_pool_holds(address, flat.nbytes),
        hash_hi, hash_lo, gen, rkey, off)
    _miss, _stale, exists = _codes()
    if rc != 0 and rc != exists:
        raise MemoryError(
            f"kv prefix publish failed (rc={rc}): the block must fit "
            "trpc_kv_store_bytes")
    meta = KvPrefixMeta(key[0], key[1], hash_hi.value, hash_lo.value,
                        gen.value, rkey.value, off.value, flat.nbytes,
                        depth, node)
    return meta, rc == 0


def publish_prefix_run(keys, first_depth: int, pages, token_spans,
                       lease_ms: int = 0, node: str = "",
                       registry: "KvRegistryClient | None" = None
                       ) -> list[tuple[KvPrefixMeta, bool]]:
    """Publishes a run of consecutive prefix blocks, the new pages of one
    prompt: block `j` of `pages` under chain key `keys[j]` at depth
    `first_depth + j` with the token span `token_spans[j]`
    (as `prefix_publish` would, block by block: out of the block the
    pages' transfer landed in where there is one), in ONE call of the
    store, which hashes the blocks' contents four at a time
    (`kv_prefix_hash_lanes`) and admits them in order.  `pages` is a
    device array, a numpy array, or the view `zerocopy.host_view` made
    of one when its transfer was started ahead; its bytes are the blocks
    end to end, equal in size.  A block the store cannot take raises
    MemoryError, the blocks before it published and those after it not.
    With a `registry` the replicas are recorded in ONE
    `put_prefix_many`; a record it refuses raises its error.  Returns
    `prefix_publish`'s (meta, fresh) per block."""
    flat = _host_flat(pages)
    n = len(keys)
    if not n or flat.nbytes % n or len(token_spans) != n:
        raise ValueError(f"{flat.nbytes} bytes are not {n} equal "
                         f"blocks with {len(token_spans)} token spans")
    nbytes = flat.nbytes // n
    if not nbytes:
        raise ValueError("empty prefix block")
    lib = load_library()
    u64s = ctypes.c_uint64 * n
    key_arr = (ctypes.c_uint64 * (2 * n))(*(k for key in keys for k in key))
    spans = [list(span) for span in token_spans]
    tok_arr, _ = _token_array(t for span in spans for t in span)
    addresses = [flat.ctypes.data + j * nbytes for j in range(n)]
    rcs = (ctypes.c_int * n)()
    hash_hi, hash_lo, gen, rkey, off = u64s(), u64s(), u64s(), u64s(), u64s()
    handled = lib.trpc_kv_prefix_publish_run(
        key_arr, first_depth, (ctypes.c_void_p * n)(*addresses), nbytes,
        tok_arr, u64s(*(len(span) for span in spans)),
        (ctypes.c_int * n)(*(lib.trpc_host_pool_holds(address, nbytes)
                             for address in addresses)),
        n, lease_ms, rcs, hash_hi, hash_lo, gen, rkey, off)
    _miss, _stale, exists = _codes()
    if any(rcs[j] != 0 and rcs[j] != exists for j in range(handled)):
        raise MemoryError(
            f"kv prefix publish failed (rc={rcs[handled - 1]}): the block "
            "must fit trpc_kv_store_bytes")
    out = [(KvPrefixMeta(key[0], key[1], hash_hi[j], hash_lo[j], gen[j],
                         rkey[j], off[j], nbytes, first_depth + j, node),
            rcs[j] == 0)
           for j, key in enumerate(keys)]
    if registry is not None:
        for answer in registry.put_prefix_many([meta for meta, _ in out],
                                               lease_ms=lease_ms):
            if isinstance(answer, RpcError):
                raise answer
    return out


def prefix_withdraw(hash_key: tuple[int, int]) -> None:
    """Evicts a local prefix block by content hash (tombstoned)."""
    rc = load_library().trpc_kv_prefix_withdraw(
        ctypes.c_uint64(hash_key[0]), ctypes.c_uint64(hash_key[1]))
    if rc != 0:
        raise KvMissError(rc, "prefix block not in the local store")


def prefix_store_count() -> int:
    return int(load_library().trpc_kv_prefix_store_count())


def prefix_hot_bytes() -> int:
    return int(load_library().trpc_kv_prefix_hot_bytes())


def prefix_cold_bytes() -> int:
    return int(load_library().trpc_kv_prefix_cold_bytes())


def prefix_registry_count() -> int:
    return int(load_library().trpc_kv_prefix_registry_count())


def prefix_registry_replicas() -> int:
    return int(load_library().trpc_kv_prefix_registry_replicas())


def prefix_counters() -> dict[str, int]:
    """Prefix-tier outcome counters since process start (promote,
    demote, hot_hits, cold_hits, dedup)."""
    lib = load_library()
    vals = [ctypes.c_uint64() for _ in range(5)]
    lib.trpc_kv_prefix_counters(*[ctypes.byref(v) for v in vals])
    return dict(zip(("promote", "demote", "hot_hits", "cold_hits",
                     "dedup"), (v.value for v in vals)))


class KvRegistryClient:
    """Thin RPC client for the registry methods over one channel."""

    def __init__(self, channel: Channel, owns_channel: bool = False):
        self._ch = channel
        self._owns = owns_channel

    def register(self, meta: KvBlockMeta, lease_ms: int = 0) -> int:
        """Records meta under a lease; returns the accepted generation.
        Raises KvExistsError while a live record holds the block."""
        try:
            resp = self._ch.call(REGISTER_METHOD, meta.pack(lease_ms))
        except RpcError as e:
            raise _kv_error(e) from None
        return struct.unpack("<Q", resp)[0]

    def lookup(self, block_id: int) -> KvBlockMeta:
        try:
            resp = self._ch.call(LOOKUP_METHOD, _req(block_id))
        except RpcError as e:
            raise _kv_error(e) from None
        return KvBlockMeta.unpack(resp)

    def evict(self, block_id: int) -> int:
        """Removes the record; returns the evicted generation."""
        try:
            resp = self._ch.call(EVICT_METHOD, _req(block_id))
        except RpcError as e:
            raise _kv_error(e) from None
        return struct.unpack("<Q", resp)[0]

    def renew(self, block_id: int, lease_ms: int = 0) -> int:
        """Extends a live record's lease; returns its generation."""
        try:
            resp = self._ch.call(RENEW_METHOD,
                                 _req(block_id, lease_ms=lease_ms))
        except RpcError as e:
            raise _kv_error(e) from None
        return struct.unpack("<Q", resp)[0]

    def _call_many(self, method: str, wires: list[bytes], entry,
                   answer) -> list:
        """The batch form of a registry call: `wires` (packed KvWire) in
        RPCs of at most MANY_MAX, each entry of the answers through
        `answer(status, fields...)`, in order."""
        out = []
        for at in range(0, len(wires), MANY_MAX):
            part = wires[at:at + MANY_MAX]
            try:
                resp = self._ch.call(
                    method, _COUNT.pack(len(part)) + b"".join(part))
            except RpcError as e:
                raise _kv_error(e) from None
            (count,) = _COUNT.unpack_from(resp)
            if (count != len(part)
                    or len(resp) != _COUNT.size + count * entry.size):
                raise RpcError(-1, f"{method} answered {count} entries in "
                               f"{len(resp)} bytes to {len(part)} records")
            out.extend(answer(*fields) for fields in
                       entry.iter_unpack(resp[_COUNT.size:]))
        return out

    def register_many(self, metas, lease_ms: int = 0) -> list:
        """`register` for many records in one RPC.  Per record, in
        order: the accepted generation, or the error `register` would
        have raised (KvExistsError, KvStaleError) as an instance, not
        raised — one record's refusal is not the others'."""
        return self._call_many(
            REGISTER_MANY_METHOD, [m.pack(lease_ms) for m in metas],
            _MANY_GEN, lambda status, gen: gen if status == 0
            else _entry_error(status, "register"))

    def lookup_many(self, block_ids) -> list:
        """`lookup` for many records in one RPC: per record its
        KvBlockMeta, or a KvMissError instance."""
        return self._call_many(
            LOOKUP_MANY_METHOD, [_req(b) for b in block_ids],
            _MANY_RECORD, lambda status, rec: KvBlockMeta.unpack(rec)
            if status == 0 else _entry_error(status, "lookup"))

    def evict_many(self, block_ids) -> list:
        """`evict` for many records in one RPC: per record the evicted
        generation, or a KvMissError instance."""
        return self._call_many(
            EVICT_MANY_METHOD, [_req(b) for b in block_ids],
            _MANY_GEN, lambda status, gen: gen if status == 0
            else _entry_error(status, "evict"))

    def put_prefix(self, meta: KvPrefixMeta,
                   lease_ms: int = 0) -> tuple[int, bool]:
        """Records one prefix-block replica; N publishers of the same
        chain key + content hash fold into ONE record with a replica
        set.  Returns (generation, fresh): fresh=False means the
        registry already held this exact replica and only renewed its
        lease (the idempotent re-offer every cache hit makes)."""
        try:
            resp = self._ch.call(PREFIX_PUT_METHOD, meta.pack(lease_ms))
        except RpcError as e:
            e = _kv_error(e)
            if isinstance(e, KvExistsError):
                return meta.generation, False
            raise e from None
        return struct.unpack("<Q", resp)[0], True

    def put_prefix_many(self, metas, lease_ms: int = 0) -> list:
        """`put_prefix` for many replica records in one round trip
        (`KvReg.PutPrefixMany`, the shape of `register_many`).  Per
        record, in order: (generation, fresh) as `put_prefix` returns
        it, or the error it would have raised (KvStaleError: a zombie
        generation, or the chain key held under another content hash) as
        an instance, not raised."""
        def answer(status, gen):
            if status == 0:
                return gen, True
            e = _entry_error(status, "put-prefix")
            return (gen, False) if isinstance(e, KvExistsError) else e

        return self._call_many(
            PREFIX_PUT_MANY_METHOD, [m.pack(lease_ms) for m in metas],
            _MANY_GEN, answer)

    def match(self, keys) -> list[KvPrefixMeta]:
        """Longest cached prefix: one replica record per live replica of
        every matched chain key, grouped in chain order (the walk stops
        at the first key with no live replica).  Empty list = nothing
        cached."""
        keys = list(keys)
        if not keys:
            return []
        req = struct.pack("<Q", len(keys)) + b"".join(
            struct.pack("<QQ", hi, lo) for hi, lo in keys)
        try:
            resp = self._ch.call(PREFIX_MATCH_METHOD, req)
        except RpcError as e:
            raise _kv_error(e) from None
        (count,) = struct.unpack_from("<Q", resp)
        return [KvPrefixMeta.unpack(resp, 8 + i * _PREFIX_WIRE.size)
                for i in range(count)]

    def close(self) -> None:
        if self._owns:
            self._ch.close()


def _block_fetch(meta: KvBlockMeta, buf) -> tuple:
    """`_fetch_round`'s entry for one block record: its node, its
    `Kv.Fetch` request at the generation looked up, its landing buffer."""
    return (meta.node, _req(meta.block_id, generation=meta.generation), buf)


class KvClient:
    """Decode-side client: registry lookups cached with generation-
    checked invalidation, per-node channel pool, one-sided landings.

    `fetch(block_id)` returns the bytes; `fetch(block_id, resp_buf=v)`
    lands them natively in `v` (an RmaBuffer view for the one-sided
    path) and returns the landed length.  A kv-stale answer invalidates
    the cached record, re-resolves, and retries once.  `fetch_many`
    lands many records with their fetches in flight together,
    `fetch_sequence` a sequence's cache of a `KvCacheLayout`, and
    `fetch_page` a page of a paged pool, one record a layer.  A landing
    fetch rides the node channel's one pipeline, which lives as long as
    the channel: one thread at a time may fetch through a client."""

    def __init__(self, registry_addr: str, use_shm: bool = True,
                 timeout_ms: int = 30000, qos_tenant: str = "",
                 qos_priority: int = 0, naming_addr: str | None = None,
                 naming_service: str = "kv"):
        self._use_shm = use_shm
        self._timeout_ms = timeout_ms
        self._qos = (qos_tenant, qos_priority)
        self._reg_ch = Channel(registry_addr, timeout_ms=timeout_ms,
                               qos_tenant=qos_tenant,
                               qos_priority=qos_priority)
        self.registry = KvRegistryClient(self._reg_ch)
        self._node_chs: dict[str, Channel] = {}
        self._node_pipes: dict = {}  # node -> its channel's pipeline
        self._cache: dict[int, KvBlockMeta] = {}
        # Optional cluster-membership view (cpp/net/naming.h registry at
        # naming_addr, service naming_service): when a fetch fails at the
        # TRANSPORT level and the cached node has left the fleet (drained
        # or died), the dead channel is dropped and the record re-resolves
        # through the registry instead of retrying a dead pid.
        self._naming = None
        self._naming_args = (naming_addr, naming_service)
        #: Lookup-cache telemetry (reads served without a registry RPC /
        #: registry round-trips / stale-triggered invalidations).
        self.cache_hits = 0
        self.cache_misses = 0
        self.invalidations = 0
        #: Fetches re-routed because the naming view said the cached
        #: node is gone (drain/crash re-resolution telemetry).
        self.node_reresolves = 0
        #: Pooled node channels dropped because their node left the
        #: naming view (the pool must not grow with membership churn).
        self.channels_evicted = 0

    #: Pool size at which creating a NEW node channel first prunes
    #: channels whose nodes left the naming view — bounds the pool to
    #: (live members + a little churn slack) instead of every node that
    #: ever served a block.
    _POOL_PRUNE_AT = 4

    def _prune_gone_channels(self) -> None:
        """Evicts pooled channels for nodes absent from the naming view
        (one resolve for the whole sweep; no view configured or registry
        unreachable = no verdict, keep everything)."""
        naming_addr, service = self._naming_args
        if naming_addr is None:
            return
        if self._naming is None:
            from brpc_tpu.rpc import naming as _naming

            self._naming = _naming.NamingClient(naming_addr,
                                                timeout_ms=self._timeout_ms)
        try:
            _version, members = self._naming.resolve(service)
        except RpcError:
            return
        live = {m.addr for m in members}
        for node in [n for n in self._node_chs if n not in live]:
            self._drop_node(node)
            self.channels_evicted += 1

    def _drop_node(self, node: str) -> None:
        """Closes the node's pipeline (cancelling what it has in flight)
        and its channel; the next fetch from the node opens new ones."""
        pipe = self._node_pipes.pop(node, None)
        if pipe is not None:
            pipe.close()
        ch = self._node_chs.pop(node, None)
        if ch is not None:
            ch.close()

    def _node_pipeline(self, node: str):
        pipe = self._node_pipes.get(node)
        if pipe is None:
            pipe = self._node_pipes[node] = self._node_channel(
                node).pipeline()
        return pipe

    def transports(self) -> dict[str, str]:
        """Live transport of each node channel ("shm_ring", "tcp")."""
        return {node: ch.transport for node, ch in self._node_chs.items()}

    def _node_channel(self, node: str) -> Channel:
        ch = self._node_chs.get(node)
        if ch is None:
            if len(self._node_chs) >= self._POOL_PRUNE_AT:
                # The pool is about to grow past the prune threshold:
                # drop channels for departed nodes first so membership
                # churn can't grow it unboundedly.
                self._prune_gone_channels()
            tenant, prio = self._qos
            # shm rings are single-connection by construction; TCP block
            # pulls spread over pooled sockets (stripe rails).
            ch = Channel(node, timeout_ms=self._timeout_ms,
                         use_shm=self._use_shm,
                         connection_type="single" if self._use_shm
                         else "pooled",
                         qos_tenant=tenant, qos_priority=prio)
            self._node_chs[node] = ch
        return ch

    def lookup(self, block_id: int, refresh: bool = False) -> KvBlockMeta:
        if not refresh:
            meta = self._cache.get(block_id)
            if meta is not None:
                self.cache_hits += 1
                return meta
        self.cache_misses += 1
        meta = self.registry.lookup(block_id)
        self._cache[block_id] = meta
        return meta

    def lookup_many(self, block_ids, refresh: bool = False) -> list:
        """`lookup` for many records: the cache first, the rest in one
        `KvReg.LookupMany`.  Per record its KvBlockMeta, or a
        KvMissError instance."""
        block_ids = list(block_ids)
        out = [None if refresh else self._cache.get(b) for b in block_ids]
        asked = [i for i, meta in enumerate(out) if meta is None]
        self.cache_hits += len(block_ids) - len(asked)
        self.cache_misses += len(asked)
        if asked:
            answers = self.registry.lookup_many(
                block_ids[i] for i in asked)
            for i, answer in zip(asked, answers):
                out[i] = answer
                if not isinstance(answer, RpcError):
                    self._cache[block_ids[i]] = answer
        return out

    def invalidate(self, block_id: int) -> None:
        if self._cache.pop(block_id, None) is not None:
            self.invalidations += 1

    def _node_gone(self, node: str) -> bool:
        """True when the naming view is configured AND `node` is not a
        member of it (the owner drained or died — its withdrawn/expired
        announcement is the authoritative 'do not retry this pid')."""
        naming_addr, service = self._naming_args
        if naming_addr is None:
            return False
        if self._naming is None:
            from brpc_tpu.rpc import naming as _naming

            self._naming = _naming.NamingClient(naming_addr,
                                                timeout_ms=self._timeout_ms)
        try:
            _version, members = self._naming.resolve(service)
        except RpcError:
            return False  # registry unreachable: no verdict, keep the node
        return all(m.addr != node for m in members)

    def fetch(self, block_id: int, resp_buf=None):
        """Bytes of block_id (or the landed length with resp_buf)."""
        last: RpcError | None = None
        # With a naming view a third attempt is budgeted: transport-dead
        # node -> drop channel + re-resolve -> fetch the re-published
        # block from its new owner.
        attempts = 3 if self._naming_args[0] is not None else 2
        for attempt in range(attempts):
            meta = self.lookup(block_id, refresh=attempt > 0)
            try:
                if resp_buf is None:
                    return self._node_channel(meta.node).call(
                        FETCH_METHOD,
                        _req(block_id, generation=meta.generation),
                        timeout_ms=self._timeout_ms)
                landed = self._fetch_round([_block_fetch(meta, resp_buf)])[0]
                if isinstance(landed, RpcError):
                    raise landed
                return landed
            except RpcError as e:
                e = _kv_error(e)
                if isinstance(e, (KvStaleError, KvMissError)):
                    last = e
                    self.invalidate(block_id)  # generation-checked
                    continue
                # Transport/chaos failure: the record MAY be fine — but
                # if the naming view says the owner left the fleet, the
                # dead channel must not be retried (it would only time
                # out again): drop it and re-resolve through the
                # registry, which the new owner re-publishes into.
                if attempt + 1 < attempts and self._node_gone(meta.node):
                    self._drop_node(meta.node)
                    self.invalidate(block_id)
                    self.node_reresolves += 1
                    last = e
                    continue
                raise
        raise last

    def _fetch_round(self, wanted, method: str = FETCH_METHOD) -> list:
        """One call of `method` (`Kv.Fetch`, `Kv.FetchPrefix`) per (node,
        request, landing buffer) of `wanted`, all in flight together: the
        requests to one node cross in ONE `pipeline.submit`, then the
        pipelines are polled until every record is in.  Per record, in
        order: the landed length, or its error as an instance (the
        one-sided direct path where the buffer is RmaBuffer-backed and
        stripe-eligible and the connection is shm/ici; else the runtime
        copies the response out on completion).  A node whose poll times
        out is dropped, so that a late completion cannot be taken for a
        later call's."""
        out: list = [None] * len(wanted)
        by_node: dict[str, list[int]] = {}
        for i, (node, _request, _buf) in enumerate(wanted):
            by_node.setdefault(node, []).append(i)
        pending = []
        for node, members in by_node.items():
            pipe = self._node_pipeline(node)
            tokens = pipe.submit(
                method, [wanted[i][1] for i in members],
                resp_bufs=[wanted[i][2] for i in members],
                timeout_ms=self._timeout_ms)
            pending.append((node, pipe, dict(zip(tokens, members))))
        for node, pipe, waiting in pending:
            while waiting:
                done = pipe.poll(max_n=len(waiting),
                                 timeout_ms=self._timeout_ms)
                if not done:
                    for i in waiting.values():
                        out[i] = RpcError(-1, "kv fetch timed out in poll")
                    self._drop_node(node)
                    break
                for c in done:
                    i = waiting.pop(c.token, None)
                    if i is None:
                        continue
                    if not c.ok:
                        out[i] = _kv_error(RpcError(c.status, c.error))
                        continue
                    if not c.in_caller_buffer and c.data is not None:
                        # The runtime handed back a view instead of
                        # landing in place (tiny responses).
                        view = memoryview(wanted[i][2]).cast("B")
                        view[:c.resp_len] = c.data.view()[:c.resp_len]
                        c.data.release()
                    out[i] = c.resp_len
        return out

    def fetch_many(self, block_ids, resp_bufs) -> list[int]:
        """Lands record `block_ids[i]` in `resp_bufs[i]` (writable
        buffers), every record's fetch in flight at once, and returns
        the landed lengths.  The lookups go through `lookup_many`; a
        record answered kv-stale or kv-miss by its node is invalidated,
        re-resolved and retried once, that record alone.  If any record
        does not land, the call fails as a whole with KvFetchManyError,
        which says which (the others' buffers hold their bytes)."""
        block_ids = list(block_ids)
        if len(resp_bufs) != len(block_ids):
            raise ValueError("resp_bufs length must match block_ids")
        load_library().trpc_kv_note_fetch_many(len(block_ids))
        lengths = [0] * len(block_ids)
        failed: dict[int, RpcError] = {}
        todo = list(range(len(block_ids)))
        for attempt in range(2):
            metas = self.lookup_many((block_ids[i] for i in todo),
                                     refresh=attempt > 0)
            asked = []
            for i, meta in zip(todo, metas):
                if isinstance(meta, RpcError):
                    failed[block_ids[i]] = meta  # the registry has none
                else:
                    asked.append((i, meta))
            landed = self._fetch_round(
                [_block_fetch(meta, resp_bufs[i]) for i, meta in asked])
            todo = []
            for (i, _meta), answer in zip(asked, landed):
                if not isinstance(answer, RpcError):
                    lengths[i] = answer
                elif (isinstance(answer, (KvStaleError, KvMissError))
                      and attempt == 0):
                    self.invalidate(block_ids[i])  # generation-checked
                    todo.append(i)
                else:
                    failed[block_ids[i]] = answer
            if not todo:
                break
        if failed:
            raise KvFetchManyError(failed)
        return lengths

    def _land_records(self, records, bufs) -> None:
        """Lands `records` ((id, bytes), in order) in `bufs`, every
        fetch in flight together (`fetch_many`): where a buffer is
        stripe-eligible and RmaBuffer-backed its record can land there
        one-sided.  A record that is missing or of another length fails
        them all (KvFetchManyError)."""
        lengths = self.fetch_many([rid for rid, _ in records], bufs)
        short = {rid: RpcError(-1, f"record of {n} bytes, layer of {want}")
                 for (rid, want), n in zip(records, lengths) if n != want}
        if short:
            raise KvFetchManyError(short)

    def fetch_sequence(self, seq_id: int, layout: KvCacheLayout, pages,
                       states=None):
        """Lands the sequence `seq_id` that `publish_sequence`
        published, for a rank that knows its length: `pages` is a
        writable C-contiguous numpy array of leading axes (pages of the
        sequence, paged layers), `states` one of leading axis (snapshot
        layers), None where the layout has none (as there, an array's
        bytes are its records end to end); views of an RmaBuffer for
        the one-sided path, which one record a region takes at a time
        (cpp/net/rma.cc `rma_landing_bind`): the others of a region
        cross the connection's window and are copied out on landing.
        One `lookup_many`, then every record of both kinds in flight in
        one round, each landing in its place; the snapshots asked for
        are those of the boundary `pages.shape[0]`.  Returns `(pages,
        states)`, each ready for one `jax.device_put`.  A record that is missing, short, or a
        snapshot taken at another boundary refuses the hand-over whole:
        KvFetchManyError names the records, and nothing is handed over
        (the landing areas then hold no sequence)."""
        paged, snapshot = layout.records(seq_id, pages.shape[0])
        if (states is None) != (not snapshot):
            raise ValueError("`states` goes with the layout's snapshot "
                             "layers")
        bufs = _cut(pages, paged)
        if snapshot:
            bufs += _cut(states, snapshot)
        note = load_library().trpc_kv_note_sequence
        try:
            self._land_records(paged + snapshot, bufs)
        except KvFetchManyError:
            note(0, 0, 0, 0, 0)
            raise
        note(len(paged), sum(n for _, n in paged), len(snapshot),
             sum(n for _, n in snapshot), 1)
        return pages, states

    def fetch_page(self, block_id: int, landing):
        """Lands the page `block_id` that `publish_page` published,
        layer `l`'s record in `landing[l]`: `fetch_sequence`'s round for
        a layout of `landing.shape[0]` paged layers and this one page.
        `landing` is a writable C-contiguous numpy array (a view of an
        RmaBuffer for the one-sided path); it is returned, whole, ready
        for one `jax.device_put`."""
        layers = landing.shape[0]
        layout = KvCacheLayout.paged(layers, landing.nbytes // layers)
        records = layout.records(block_id, 1)[0]
        self._land_records(records, _cut(landing, records))
        return landing

    # ---- content-addressed prefix cache (ISSUE 17) ----

    def match_prefix(self, tokens,
                     block_tokens: int = 0) -> list[list[KvPrefixMeta]]:
        """Longest cached prefix for `tokens`: replica groups in chain
        order (groups[i] = every live replica of prefix block i).  An
        empty list means nothing is cached — full recompute."""
        keys = prefix_chain(tokens, block_tokens)
        if not keys:
            return []
        records = self.registry.match(keys)
        groups: list[list[KvPrefixMeta]] = []
        cur = None
        for r in records:
            if r.key != cur:
                groups.append([])
                cur = r.key
            groups[-1].append(r)
        return groups

    @staticmethod
    def prefix_hint(groups: list[list[KvPrefixMeta]]) -> str:
        """The routing hint for this prompt: the node holding the
        DEEPEST matched block ("host:port", "" when nothing matched).
        Pass it to ClusterChannel.call(..., hint=...) so decode/prefill
        traffic lands where the cache already is — unless bounded load
        vetoes."""
        return groups[-1][0].node if groups else ""

    def fetch_prefix_blocks(self, groups, landing=None,
                            window: int = 16) -> list:
        """Lands the matched run `groups` (`match_prefix`'s answer, or a
        slice of it) block by block in chain order and returns the landed
        blocks, each a flat uint8 array of its block's length.  The
        `Kv.FetchPrefix` calls ride the node channel's pipeline as
        `Kv.Fetch` does, at most `window` blocks in flight (a block over
        `trpc_stripe_threshold` on the shm ring crosses the connection's
        one-sided window, so `window` blocks must fit
        `trpc_rma_window_bytes`).  Block `i` lands in `landing[i]`
        (writable C-contiguous numpy arrays of the blocks' sizes, or one
        array whose leading axis is the blocks: rows of a registered
        buffer for the one-sided landing); without `landing`, in a
        recycled block of the host pool (`zerocopy.landing_block`).
        Fail-over across replicas and whole-or-nothing per block, as
        ever: a replica that answers stale, faulted, short or not at all
        serves nothing and the block's next replica is asked; where every
        replica of a block fails the cacheable prefix ends there and the
        list is that much shorter than `groups` (callers recompute the
        rest; blocks behind the hole are never handed out)."""
        landed: list = []
        for at in range(0, len(groups), max(1, window)):
            part = groups[at:at + max(1, window)]
            bufs = [(zerocopy.landing_block(group[0].length)
                     if landing is None
                     else landing[at + i].reshape(-1).view(np.uint8))
                    for i, group in enumerate(part)]
            ok = [False] * len(part)
            replica = 0
            while True:
                asked = [i for i, group in enumerate(part)
                         if not ok[i] and replica < len(group)]
                if not asked:
                    break
                answers = self._fetch_round(
                    [(part[i][replica].node, part[i][replica].pack(),
                      bufs[i]) for i in asked], PREFIX_FETCH_METHOD)
                for i, answer in zip(asked, answers):
                    # Stale, chunk-faulted, short or dead replica: the
                    # block is never admitted partially.
                    ok[i] = answer == part[i][replica].length
                replica += 1
            run = ok.index(False) if False in ok else len(ok)
            landed.extend(bufs[:run])
            if run < len(part):
                break
        return landed

    def fetch_prefix(self, tokens, block_tokens: int = 0) -> list[bytes]:
        """Fetches every cached prefix block for `tokens` in chain order
        as `bytes`: `match_prefix`, then `fetch_prefix_blocks` (its
        fail-over and whole-or-nothing rules), each landed block copied
        out once.  The returned list may be shorter than the match when
        every replica of a block fails — the cacheable prefix simply
        ends there (callers recompute the rest).  A caller that goes on
        to the device keeps the landed arrays instead
        (`fetch_prefix_blocks`)."""
        return [block.tobytes() for block in self.fetch_prefix_blocks(
            self.match_prefix(tokens, block_tokens))]

    def close(self) -> None:
        for node in list(self._node_chs):
            self._drop_node(node)
        if self._naming is not None:
            self._naming.close()
            self._naming = None
        self._reg_ch.close()
