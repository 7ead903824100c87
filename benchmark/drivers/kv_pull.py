"""Driver `kv_pull`: one 128-token page of a prompt's latent KV cache goes
from a prefill pool in HBM to a decode pool in HBM through the KV plane
(brpc_tpu/rpc/kv.py over cpp/net/kvstore.cc), one record a layer.

One process holds the chip, both pools (brpc_tpu/models/kv_pool.py), the
Server with the block store and the registry, and the decode side's
KvClient; the configuration file says what that stands for.  One call is
one block:

1. produce: one program makes a fresh page from the one before (the
   reference's rule, reference_kv.next_page) and writes it into the
   prefill pool at a seeded slot; untimed, the yardstick's;
2. `read_page` of that slot and `zerocopy.host_view` of the page (the
   D2H starts), then `kv.publish_page`: the bytes waited for, 61 records
   published out of the slab, one `register_many`;
3. `KvClient.fetch_page`: one `lookup_many`, 61 `Kv.Fetch` in flight on
   the node channel's pipeline, landed in the landing buffer;
4. `jax.device_put` of the landed page and `write_page` into a seeded
   slot of the decode pool, ended by `block_until_ready`;
5. the compare, on the device, every block: the decode pool's slot
   against the page produced in 1, exact, folded into one device scalar
   that is fetched once, after the window (launched before the next
   write, so a later block cannot overwrite what it reads);
6. `kv.withdraw_page`: one `evict_many`, 61 withdraws; the slab's part
   is free again.

The call's sample runs from just before 2's `read_page` to the end of
4's `block_until_ready`.  A closed loop on one client thread keeps
`blocks_in_flight` blocks open: the oldest goes through the rest of 2 to
6 while the D2H transfers of the others are on their way, then a new one
is started.  Staging, the registry and the fetch are reached only through
the program's own entries, so a change inside them shows; the registry's
three round trips are timed by a wrapper round the client the program is
given (`register`, `lookup`, `evict`: intervals, not annotated, since
they lie inside `publish`, `fetch` and `withdraw`).

A page given to `host_view` has never been fetched (`SendOnce`), and no
32-bit word of a page equals the same word of an earlier one
(reference_kv.py), so bytes a record's fetch never wrote, or wrote from
another record or another block, fail the compare, and the recycled
landing buffer needs no poisoning.  After the window both pools' per-slot
checksums are compared with the reference's for the same sequence, which
it follows from the initial checksums (no third pool fits at the timed
size); where the pools are small the reference also holds them whole and
every byte is compared.
"""

from __future__ import annotations

import collections
import dataclasses
import random
import shutil
import time

from benchmark import counters, reference_kv
from benchmark.evidence import Evidence
from benchmark.payload import SendOnce

SHM_FREE_NEEDED = 2 << 30     # as served_echo: shm files are not fallocated
CALL_TIMEOUT_MS = 30000
LEASE_MS = 600000
LANDING_BLOCKS = 2
# Below this the reference also holds whole pools and every byte of both
# is compared; the checksums are compared at every size.
WHOLE_POOLS_UNDER = 64 << 20
# glibc serves a block from fresh mmapped pages while it is larger than
# its mmap threshold, and raises that threshold to the size of every
# mmapped block it is given back, up to 32 MB.  A page's host copy is
# 9 MB: whether it lands in fresh pages (2 ms of page faults more) or in
# recycled heap depends on what the process happened to free before, and
# a process that has run for a while has the threshold at its top.
SETTLED_MMAP_BLOCK = (32 << 20) - (1 << 16)


@dataclasses.dataclass
class _Block:
    number: int            # its id in the KV plane, from 1, never reused
    decode_slot: int
    page: object           # as produced: what the decode slot must hold
    read: object           # as read back from the prefill pool
    pending: object        # `read`'s bytes, on their way to the host
    t0: float
    handed_over: bool = False


class _TimedRegistry:
    """The registry client the program is given, each batch call an
    interval of the benchmark's spans; everything else is the client's."""

    NAMES = {"register_many": "register", "lookup_many": "lookup",
             "evict_many": "evict"}

    def __init__(self, real, spans):
        self._real, self._spans = real, spans

    def __getattr__(self, name):
        call = getattr(self._real, name)
        span = self.NAMES.get(name)
        if span is None:
            return call

        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return call(*args, **kwargs)
            finally:
                self._spans.add(span, t0, time.perf_counter())

        return timed


def run(ctx) -> Evidence:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.models import kv_pool
    from brpc_tpu.rpc import Channel, RmaBuffer, Server, _lib, kv, zerocopy

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    layers = int(cfg["num_hidden_layers"])
    width = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    tokens = int(mix["page_tokens"])
    pages = int(mix["pool_pages"])
    depth = int(mix["blocks_in_flight"])
    warm_calls = int(mix["warm_calls"])
    record_bytes = tokens * width * 2
    block_bytes = layers * record_bytes
    if (layers != int(mix["records_per_block"])
            or record_bytes != int(mix["record_bytes"])):
        raise ValueError(
            f"{ctx.cell.name}: the mix says {mix['records_per_block']} "
            f"records of {mix['record_bytes']} B, the configuration's "
            f"widths give {layers} of {record_bytes} B")
    if int(mix["verify_group"]) != 1 or tokens % 2:
        raise ValueError(
            "a block is compared before the next is written (verify_group "
            "1), and a page's tokens pair up into 32-bit words")
    words_per_page = block_bytes // 4
    device = ctx.devices[0]
    spans = ctx.spans
    now = time.perf_counter

    shm_free = shutil.disk_usage("/dev/shm").free
    if shm_free < SHM_FREE_NEEDED:
        raise SystemExit(
            f"/dev/shm has {shm_free} bytes free; the transports need "
            f"{SHM_FREE_NEEDED} to be safe from SIGBUS")
    built = _lib.ensure_built()
    _lib.load_library()

    # ---- the yardstick's own programs --------------------------------
    def bm_kv_produce(pool, slot, prev):
        page = reference_kv.next_page(prev)
        return kv_pool.kv_write_page(pool, slot, page), page

    def bm_kv_verify(bad, pool, slot, page):
        return bad + jnp.any(
            kv_pool.kv_read_page(pool, slot) != page).astype(jnp.uint32)

    def bm_kv_pool_checksums(pool):
        return jax.lax.map(reference_kv.page_checksum, pool)

    produce = jax.jit(bm_kv_produce, donate_argnums=0)
    verify = jax.jit(bm_kv_verify)
    pool_checksums = jax.jit(bm_kv_pool_checksums)

    with jax.default_device(device):
        prefill = kv_pool.seeded_pool(ctx.seed, pages, layers, tokens,
                                      width)
        decode = kv_pool.seeded_pool(ctx.seed + 1, pages, layers, tokens,
                                     width)
        first = kv_pool.read_page(prefill, 0)
        initial_sums = jax.device_get(
            (pool_checksums(prefill), pool_checksums(decode),
             reference_kv.page_checksum(first)))
        whole = None
        if 2 * pages * block_bytes < WHOLE_POOLS_UNDER:
            whole = (np.array(prefill), np.array(decode), np.array(first))
        bad = jnp.uint32(0)
    jax.block_until_ready((prefill, decode, bad))

    np.empty(SETTLED_MMAP_BLOCK, dtype=np.uint8)  # freed at once: above
    kv.reset()     # this process's store and registry are this run's
    srv = Server()
    srv.enable_kv_store()
    srv.enable_kv_registry()
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    slab = RmaBuffer(depth * block_bytes)
    land = RmaBuffer(LANDING_BLOCKS * block_bytes)
    landings = np.frombuffer(land.view, dtype=np.uint16).reshape(
        LANDING_BLOCKS, layers, tokens, width)
    reg = cli = None
    try:
        reg = _TimedRegistry(
            kv.KvRegistryClient(Channel(addr, timeout_ms=CALL_TIMEOUT_MS),
                                owns_channel=True), spans)
        cli = kv.KvClient(addr, timeout_ms=CALL_TIMEOUT_MS,
                          use_shm=cfg["channel"]["use_shm"])
        cli.registry = _TimedRegistry(cli.registry, spans)

        guard = SendOnce()
        slots = random.Random(ctx.seed)
        last = first
        opened: collections.deque[_Block] = collections.deque()
        sequence: list[tuple] = []     # what the reference follows
        finished: list[tuple[float, float]] = []   # (end, seconds)
        failed_at: list[float] = []
        blocks = 0

        def start_block() -> None:
            nonlocal prefill, last, blocks
            blocks += 1
            prefill_slot = slots.randrange(pages)
            decode_slot = slots.randrange(pages)
            with spans.span("produce"):
                prefill, last = produce(prefill, prefill_slot, last)
            t0 = now()
            with spans.span("read"):
                page = kv_pool.read_page(prefill, prefill_slot)
            guard.claim(page)
            with spans.span("d2h"):
                pending, _owner = zerocopy.host_view(page)
            block = _Block(blocks, decode_slot, last, page, pending, t0)
            opened.append(block)
            sequence.append((prefill_slot, decode_slot, block))

        def finish_block() -> None:
            nonlocal decode, bad
            block = opened.popleft()
            offset = block.number % depth * block_bytes
            landing = landings[block.number % LANDING_BLOCKS]
            # Where the page is host-visible as it stands (the CPU's
            # rehearsal) there is no transfer and `pending` is its bytes.
            staged = isinstance(block.pending, zerocopy.PendingView)
            with spans.span("d2h_wait"):
                if staged:
                    block.pending.resolve()
            with spans.span("publish"):
                kv.publish_page(block.number,
                                block.pending if staged else block.read,
                                slab, offset=offset, lease_ms=LEASE_MS,
                                node=addr, registry=reg)
            try:
                with spans.span("fetch"):
                    cli.fetch_page(block.number, landing)
            except kv.KvFetchManyError as e:
                print(f"# block {block.number} failed: {e}", flush=True)
                failed_at.append(now())
            else:
                with spans.span("h2d"):
                    back = jax.block_until_ready(
                        jax.device_put(landing, device))
                with spans.span("write"):
                    decode = jax.block_until_ready(kv_pool.write_page(
                        decode, block.decode_slot, back))
                t1 = now()
                block.handed_over = True
                finished.append((t1, t1 - block.t0))
                with spans.span("verify"):
                    bad = verify(bad, decode, block.decode_slot, block.page)
            with spans.span("withdraw"):
                kv.withdraw_page(block.number, layers, registry=reg)
                for layer in range(layers):
                    cli.invalidate(kv.page_record_id(block.number, layer))
            block.page = block.read = block.pending = None

        # ---- one untimed window, then the timed one without a pause ----
        for _ in range(depth):
            start_block()
        while len(finished) + len(failed_at) < warm_calls:
            finish_block()
            start_block()
        before = counters.read_native()
        compiles_before = ctx.compiles.count
        t_open = now()
        deadline = t_open + ctx.seconds
        trace_at = deadline - min(float(mix["trace_seconds"]), ctx.seconds)
        traced_from = None
        while True:
            finish_block()
            t = now()
            if t >= deadline:
                t_close = t
                break
            if ctx.trace and traced_from is None and t >= trace_at:
                ctx.start_trace()
                traced_from = now()
            start_block()
        compiles_in_window = ctx.compiles.count - compiles_before
        after = counters.read_native()
        traced = None
        if traced_from is not None:
            ctx.stop_trace()
            traced = (traced_from, t_close)
        while opened:
            finish_block()
        mismatched = int(bad)
        transports = cli.transports()

        # ---- both pools against the reference -------------------------
        followed = [(p, d, block.handed_over) for p, d, block in sequence]
        got_sums = jax.device_get(
            (pool_checksums(prefill), pool_checksums(decode)))
        want_sums = reference_kv.kv_disagg_reference_checksums(
            *initial_sums, words_per_page, followed)
        slots_differ = sum(
            int(got) != want for got_pool, want_pool in
            zip(got_sums, want_sums) for got, want in
            zip(got_pool, want_pool))
        if whole is not None:
            want_pools = reference_kv.kv_disagg_reference(
                *(jnp.asarray(x) for x in whole), followed)
            slots_differ += sum(
                int(np.any(np.asarray(got[s]) != np.asarray(want[s])))
                for got, want in zip((prefill, decode), want_pools)
                for s in range(pages))
    finally:
        if cli is not None:
            cli.close()
        if reg is not None:
            reg.close()
        srv.stop()
        slab.free()
        land.free()

    counted = [(end, s) for end, s in finished if t_open < end <= t_close]
    not_ok = sum(1 for t in failed_at if t > t_open)
    attempted = sum(1 for end, _ in finished if end > t_open) + not_ok
    failed = not_ok + mismatched + slots_differ
    transport = transports.get(addr, "")
    yardstick = sum(spans.total(n, t_open, t_close)
                    for n in ("produce", "verify"))
    return Evidence(
        t_open=t_open, t_close=t_close,
        call_s=[s for _, s in counted], call_end=[end for end, _ in counted],
        bytes_per_call=block_bytes, attempted=attempted, failed=failed,
        correct=(failed == 0 and not failed_at
                 and transport == cfg["transport"]),
        compiles_in_window=compiles_in_window, spans=spans,
        counters=counters.delta(before, after),
        traced=traced,
        notes={
            "transport": transport,
            "transport_expected": cfg["transport"],
            "native_build": built,
            "seed_checksum": int(initial_sums[2]),
            "blocks_produced": blocks,
            "blocks_mismatched_on_device": mismatched,
            "pool_slots_differing_from_reference": slots_differ,
            "whole_pools_compared": whole is not None,
            "records_per_block": layers, "record_bytes": record_bytes,
            "pool_bytes": pages * block_bytes,
            "yardstick_share_of_window": yardstick / (t_close - t_open),
        })
