"""Driver `kv_seq_pull`: a prompt's cache of two kinds goes from a prefill
rank's pools in HBM to a decode rank's pools in HBM as one unit through
the KV plane (brpc_tpu/rpc/kv.py over cpp/net/kvstore.cc): the pages of
its latent-attention layers, one record a layer a page, and one snapshot
of the recurrent state of each linear-attention layer, taken at the
pages' boundary.

One process holds the chip, all four pools (brpc_tpu/models/kv_pool.py:
a rank's page pool and its state pool), the Server with the block store
and the registry, and the decode side's KvClient; the configuration file
says what that stands for.  One call is one sequence:

1. produce: one program makes a fresh sequence from the one before (the
   reference's rule, reference_kv_hybrid.next_sequence) and writes its
   pages into seeded slots of the prefill page pool and its states into
   a seeded slot of the prefill state pool; untimed, the yardstick's;
2. `read_pages` of those slots and `read_page` of the state slot, and
   `zerocopy.host_view` of both (the D2H starts), then
   `kv.publish_sequence`: the bytes waited for, every record of both
   kinds published out of the slab, one `register_many`;
3. `KvClient.fetch_sequence`: one `lookup_many`, every record's
   `Kv.Fetch` in flight on the node channel's pipeline, each landed in
   its place of the landing area;
4. `jax.device_put` of the landed pages and states, `write_pages` and
   `write_page` into seeded slots of the decode pools, ended by
   `block_until_ready`;
5. the compare, on the device, every sequence: the decode pools' slots
   against what 1 produced, pages and states, exact, folded into one
   device scalar that is fetched once, after the window (launched before
   the next write, so a later sequence cannot overwrite what it reads);
6. `kv.withdraw_sequence`: one `evict_many`, the withdraws; the slab's
   part is free again.

The call's sample runs from just before 2's reads to the end of 4's
`block_until_ready`.  A closed loop on one client thread keeps
`sequences_in_flight` sequences open: the oldest goes through the rest
of 2 to 6 while the D2H transfers of the others are on their way, then a
new one is started.  Staging, the registry and the fetch are reached
only through the program's own entries; the registry's three round trips
are timed by `kv_pull`'s wrapper round the client the program is given
(`register`, `lookup`, `evict`: intervals, not annotated, since they lie
inside `publish`, `fetch` and `withdraw`).

An array given to `host_view` has never been fetched (`SendOnce`), and
no 32-bit word of a sequence's cache equals the same word of the
sequence before, pages or states (reference_kv_hybrid.py), so bytes a
record's fetch never wrote, or a snapshot left from an earlier sequence,
fail the compare, and the recycled landing area needs no poisoning.  A
hand-over the program refuses (a record missing or short, a snapshot of
another boundary) writes nothing and counts in `failed`.  After the
window the per-slot checksums of all four pools are compared with the
reference's for the same sequence, which it follows from the initial
checksums (no third pool fits at the timed size); where the pools are
small the reference also holds them whole and every byte is compared.
"""

from __future__ import annotations

import collections
import dataclasses
import random
import shutil
import time

from benchmark import counters, reference_kv, reference_kv_hybrid
from benchmark.drivers.kv_pull import (CALL_TIMEOUT_MS, LEASE_MS,
                                       SHM_FREE_NEEDED, _TimedRegistry)
from benchmark.evidence import Evidence
from benchmark.payload import SendOnce

STATE_ROW_WORDS = 128         # a state record in the pool: rows of 128 words
# Below this the reference also holds whole pools and every byte of all
# four is compared; the checksums are compared at every size.
WHOLE_POOLS_UNDER = 256 << 20
POOLS = ("prefill_pages", "prefill_states", "decode_pages", "decode_states")


@dataclasses.dataclass
class _Sequence:
    number: int            # its id in the KV plane, from 1, never reused
    slots: tuple           # the reference's entry, less `handed_over`
    produced: tuple        # (pages, states): what the decode slots must hold
    read: tuple            # as read back from the prefill pools
    pending: tuple         # `read`'s bytes, on their way to the host
    t0: float
    handed_over: bool = False


def geometry(cfg: dict, mix: dict) -> dict:
    """The cell's sizes from the configuration's widths and the mix's
    counts, and a refusal where the mix's own sizes say otherwise.  The
    mix takes the first `page_layers` of the configuration's
    full-attention layers and the first `snapshot_layers` of its KDA
    layers (all of them at the timed size, fewer in a rehearsal)."""
    lin = cfg["linear_attn_config"]
    tokens = int(mix["page_tokens"])
    pages = int(mix["prompt_tokens"]) // tokens
    width = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    heads, head_dim = int(lin["num_heads"]), int(lin["head_dim"])
    snapshot_bytes = (heads * head_dim * head_dim * 4
                      + 3 * (int(lin["short_conv_kernel_size"]) - 1)
                      * heads * head_dim * 2)
    paged = list(lin["full_attn_layers"])[:int(mix["page_layers"])]
    snapshot = list(lin["kda_layers"])[:int(mix["snapshot_layers"])]
    g = {
        "pages": pages, "page_tokens": tokens, "width": width,
        "paged_layers": paged, "snapshot_layers": snapshot,
        "page_record_bytes": tokens * width * 2,
        "snapshot_record_bytes": snapshot_bytes,
        "page_records": pages * len(paged),
        "snapshot_records": len(snapshot),
        "state_rows": snapshot_bytes // (2 * STATE_ROW_WORDS),
    }
    g["bytes_per_call"] = (g["page_records"] * g["page_record_bytes"]
                           + g["snapshot_records"] * snapshot_bytes)
    differs = {k: (mix[k], g[k]) for k in (
        "page_records", "page_record_bytes", "snapshot_records",
        "snapshot_record_bytes", "bytes_per_call") if k in mix
        and int(mix[k]) != g[k]}
    if (differs or len(paged) != int(mix["page_layers"])
            or len(snapshot) != int(mix["snapshot_layers"])
            or pages * tokens != int(mix["prompt_tokens"])
            or snapshot_bytes % (2 * STATE_ROW_WORDS) or tokens % 2
            or g["state_rows"] % 2 or int(mix["verify_group"]) != 1):
        raise ValueError(
            f"the mix and the configuration's widths disagree (mix, "
            f"widths): {differs}; a prompt is whole pages of an even "
            f"number of tokens, and a sequence is compared before the "
            f"next is written (verify_group 1)")
    return g


def run(ctx) -> Evidence:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.models import kv_pool
    from brpc_tpu.rpc import Channel, RmaBuffer, Server, _lib, kv, zerocopy

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    g = geometry(cfg, mix)
    n_pages, tokens, width = g["pages"], g["page_tokens"], g["width"]
    paged, snapshot = g["paged_layers"], g["snapshot_layers"]
    pool_pages = int(mix["pool_pages"])
    state_slots = int(mix["state_slots"])
    depth = int(mix["sequences_in_flight"])
    warm_calls = int(mix["warm_calls"])
    seq_bytes = g["bytes_per_call"]
    page_shape = (len(paged), tokens, width)
    state_shape = (len(snapshot), g["state_rows"], STATE_ROW_WORDS)
    pages_bytes = g["page_records"] * g["page_record_bytes"]
    # The model's layers in their order, each of its kind.
    order = sorted(paged + snapshot)
    layout = kv.KvCacheLayout(
        tuple(kv.PAGED if layer in paged else kv.SNAPSHOT
              for layer in order),
        tuple(g["page_record_bytes"] if layer in paged
              else g["snapshot_record_bytes"] for layer in order))
    assert layout.sequence_bytes(n_pages) == seq_bytes
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
    def bm_kvh_produce(page_pool, state_pool, page_slots, state_slot,
                       prev_pages, prev_states):
        pages, states = reference_kv_hybrid.next_sequence(
            prev_pages, prev_states)
        return (kv_pool.kv_write_pages(page_pool, page_slots, pages),
                kv_pool.kv_write_page(state_pool, state_slot, states),
                pages, states)

    def bm_kvh_verify(bad, page_pool, state_pool, page_slots, state_slot,
                      pages, states):
        differ = (jnp.any(kv_pool.kv_read_pages(page_pool, page_slots)
                          != pages)
                  | jnp.any(kv_pool.kv_read_page(state_pool, state_slot)
                            != states))
        return bad + differ.astype(jnp.uint32)

    def bm_kvh_pool_checksums(pool):
        return jax.lax.map(reference_kv.page_checksum, pool)

    produce = jax.jit(bm_kvh_produce, donate_argnums=(0, 1))
    verify = jax.jit(bm_kvh_verify)
    pool_checksums = jax.jit(bm_kvh_pool_checksums)

    def slot_array(slots):
        # A host array: the program takes it as an argument, where a
        # device array made here would cost a launch of its own.
        return np.asarray(slots, dtype=np.int32)

    with jax.default_device(device):
        pools = {
            "prefill_pages": kv_pool.seeded_pool(
                ctx.seed, pool_pages, *page_shape),
            "prefill_states": kv_pool.seeded_pool(
                ctx.seed + 1, state_slots, *state_shape),
            "decode_pages": kv_pool.seeded_pool(
                ctx.seed + 2, pool_pages, *page_shape),
            "decode_states": kv_pool.seeded_pool(
                ctx.seed + 3, state_slots, *state_shape),
        }
        first = (kv_pool.read_pages(pools["prefill_pages"],
                                    slot_array(range(n_pages))),
                 kv_pool.read_page(pools["prefill_states"], 0))
        initial_sums = {name: jax.device_get(pool_checksums(pool))
                        for name, pool in pools.items()}
        first_sums = reference_kv_hybrid.sequence_checksums(*first)
        whole = None
        if sum(pool.nbytes for pool in pools.values()) < WHOLE_POOLS_UNDER:
            whole = ({name: np.array(pool) for name, pool in pools.items()},
                     tuple(np.array(x) for x in first))
        bad = jnp.uint32(0)
    jax.block_until_ready((pools, bad))

    kv.reset()     # this process's store and registry are this run's
    srv = Server()
    srv.enable_kv_store()
    srv.enable_kv_registry()
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    slab = RmaBuffer(depth * seq_bytes)
    land = RmaBuffer(depth * seq_bytes)
    landed = np.frombuffer(land.view, dtype=np.uint16)
    seq_words = seq_bytes // 2
    landings = [
        (landed[i * seq_words:i * seq_words + pages_bytes // 2].reshape(
            (n_pages,) + page_shape),
         landed[i * seq_words + pages_bytes // 2:(i + 1) * seq_words]
         .reshape(state_shape))
        for i in range(depth)]
    reg = cli = None
    try:
        reg = _TimedRegistry(
            kv.KvRegistryClient(Channel(addr, timeout_ms=CALL_TIMEOUT_MS),
                                owns_channel=True), spans)
        cli = kv.KvClient(addr, timeout_ms=CALL_TIMEOUT_MS,
                          use_shm=cfg["channel"]["use_shm"])
        cli.registry = _TimedRegistry(cli.registry, spans)

        guard = SendOnce()
        rng = random.Random(ctx.seed)
        last = first
        opened: collections.deque[_Sequence] = collections.deque()
        followed: list[_Sequence] = []     # what the reference follows
        finished: list[tuple[float, float]] = []   # (end, seconds)
        failed_at: list[float] = []

        def start_sequence() -> None:
            nonlocal last
            slots = (tuple(rng.sample(range(pool_pages), n_pages)),
                     rng.randrange(state_slots),
                     tuple(rng.sample(range(pool_pages), n_pages)),
                     rng.randrange(state_slots))
            with spans.span("produce"):
                (pools["prefill_pages"], pools["prefill_states"],
                 *last) = produce(
                    pools["prefill_pages"], pools["prefill_states"],
                    slot_array(slots[0]), slots[1], *last)
            t0 = now()
            with spans.span("read"):
                read = (kv_pool.read_pages(pools["prefill_pages"],
                                           slot_array(slots[0])),
                        kv_pool.read_page(pools["prefill_states"],
                                          slots[1]))
            for array in read:
                guard.claim(array)
            with spans.span("d2h"):
                pending = tuple(zerocopy.host_view(array)[0]
                                for array in read)
            seq = _Sequence(len(followed) + 1, slots, tuple(last), read,
                            pending, t0)
            opened.append(seq)
            followed.append(seq)

        def finish_sequence() -> None:
            nonlocal bad
            seq = opened.popleft()
            offset = seq.number % depth * seq_bytes
            landing = landings[seq.number % depth]
            # Where an array is host-visible as it stands (the CPU's
            # rehearsal) there is no transfer and `pending` is its bytes.
            staged = [isinstance(view, zerocopy.PendingView)
                      for view in seq.pending]
            with spans.span("d2h_wait"):
                for view, is_staged in zip(seq.pending, staged):
                    if is_staged:
                        view.resolve()
            with spans.span("publish"):
                kv.publish_sequence(
                    seq.number, layout,
                    *(view if is_staged else array for view, is_staged,
                      array in zip(seq.pending, staged, seq.read)),
                    slab, offset=offset, lease_ms=LEASE_MS, node=addr,
                    registry=reg)
            try:
                with spans.span("fetch"):
                    cli.fetch_sequence(seq.number, layout, *landing)
            except kv.KvFetchManyError as e:
                print(f"# sequence {seq.number} refused: {e}", flush=True)
                failed_at.append(now())
            else:
                with spans.span("h2d"):
                    back = jax.block_until_ready(
                        jax.device_put(landing, device))
                with spans.span("write"):
                    pools["decode_pages"] = kv_pool.write_pages(
                        pools["decode_pages"], slot_array(seq.slots[2]),
                        back[0])
                    pools["decode_states"] = kv_pool.write_page(
                        pools["decode_states"], seq.slots[3], back[1])
                    jax.block_until_ready(
                        (pools["decode_pages"], pools["decode_states"]))
                t1 = now()
                seq.handed_over = True
                finished.append((t1, t1 - seq.t0))
                with spans.span("verify"):
                    bad = verify(bad, pools["decode_pages"],
                                 pools["decode_states"],
                                 slot_array(seq.slots[2]), seq.slots[3],
                                 *seq.produced)
            with spans.span("withdraw"):
                kv.withdraw_sequence(seq.number, layout, n_pages,
                                     registry=reg)
                for record_id in layout.record_ids(seq.number, n_pages):
                    cli.invalidate(record_id)
            seq.produced = seq.read = seq.pending = None

        # ---- one untimed window, then the timed one without a pause ----
        for _ in range(depth):
            start_sequence()
        while len(finished) + len(failed_at) < warm_calls:
            finish_sequence()
            start_sequence()
        before = counters.read_native()
        compiles_before = ctx.compiles.count
        t_open = now()
        deadline = t_open + ctx.seconds
        trace_at = deadline - min(float(mix["trace_seconds"]), ctx.seconds)
        traced_from = None
        while True:
            finish_sequence()
            t = now()
            if t >= deadline:
                t_close = t
                break
            if ctx.trace and traced_from is None and t >= trace_at:
                ctx.start_trace()
                traced_from = now()
            start_sequence()
        compiles_in_window = ctx.compiles.count - compiles_before
        after = counters.read_native()
        traced = None
        if traced_from is not None:
            ctx.stop_trace()
            traced = (traced_from, t_close)
        while opened:
            finish_sequence()
        mismatched = int(bad)
        transports = cli.transports()

        # ---- all four pools against the reference ----------------------
        steps = [seq.slots + (seq.handed_over,) for seq in followed]
        got_sums = {name: jax.device_get(pool_checksums(pool))
                    for name, pool in pools.items()}
        want_sums = reference_kv_hybrid.kv_hybrid_reference_checksums(
            initial_sums, first_sums, len(paged) * tokens * width // 2,
            state_shape[0] * state_shape[1] * state_shape[2] // 2, steps)
        slots_differ = sum(
            int(got) != want for name in POOLS
            for got, want in zip(got_sums[name], want_sums[name]))
        if whole is not None:
            want_pools = reference_kv_hybrid.kv_hybrid_reference(
                {name: jnp.asarray(pool) for name, pool in whole[0].items()},
                tuple(jnp.asarray(x) for x in whole[1]), steps)
            slots_differ += sum(
                int(np.any(np.asarray(pools[name][s])
                           != np.asarray(want_pools[name][s])))
                for name in POOLS for s in range(pools[name].shape[0]))
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
        bytes_per_call=seq_bytes, attempted=attempted, failed=failed,
        correct=(failed == 0 and not failed_at
                 and transport == cfg["transport"]),
        compiles_in_window=compiles_in_window, spans=spans,
        counters=counters.delta(before, after),
        traced=traced,
        notes={
            "transport": transport,
            "transport_expected": cfg["transport"],
            "native_build": built,
            "seed_checksum": first_sums[1],
            "sequences_produced": len(followed),
            "sequences_refused": len(failed_at),
            "sequences_mismatched_on_device": mismatched,
            "pool_slots_differing_from_reference": slots_differ,
            "whole_pools_compared": whole is not None,
            "pages_per_sequence": n_pages,
            "page_records": g["page_records"],
            "page_record_bytes": g["page_record_bytes"],
            "snapshot_records": g["snapshot_records"],
            "snapshot_record_bytes": g["snapshot_record_bytes"],
            "pool_bytes": {name: int(pool.nbytes)
                           for name, pool in pools.items()},
            "yardstick_share_of_window": yardstick / (t_close - t_open),
        })
