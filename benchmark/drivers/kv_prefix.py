"""Driver `kv_prefix`: multi-turn sessions restore their prompt's prefix
from the content-addressed KV pool (brpc_tpu/rpc/kv.py over
cpp/net/kvstore.cc: chain keys and content hashes, `KvReg.Match`,
`Kv.FetchPrefix`, the two-tier store under its hot and total budgets)
into an admitting rank's pool in HBM, and publish the pages they had to
prefill.

One process holds the chip, both pools (brpc_tpu/models/kv_pool.py: the
rank whose prefill makes new pages and the rank that admits a turn), the
Server with the store and the registry, and the admitting side's
KvClient; the configuration file says what that stands for.  One call is
one turn of one session (reference_kv_prefix.turns says whose):

1. `KvClient.match_prefix(tokens)`: the chain keys of the prompt's full
   pages, one `KvReg.Match`;
2. the matched run, a window of `fetch_window_pages` blocks at a time:
   `KvClient.fetch_prefix_blocks` into the landing area (every block of
   the window in flight on the node channel's pipeline), one
   `jax.device_put` of what landed, `write_pages` into the turn's slots
   of the admitting pool, ended by `block_until_ready`.  A block that
   answers kv-stale or kv-miss ends the run: the rest is prefilled;
3. the compare, on the device: every restored page in its slot against
   the reference's page, folded into one device scalar fetched once,
   after the window;
4. the pages not restored, a window at a time: the produce program (the
   yardstick's stand-in for a prefill) writes them into seeded slots of
   the producing pool and into the turn's slots of the admitting pool;
   `read_pages` of the producing pool's slots and `zerocopy.host_view`
   (the transfer of a window starts while the window before is
   published); `kv.publish_prefix_run`: each page under its chain key
   and content hash, out of the block its transfer landed in, and one
   `KvReg.PutPrefixMany`;
5. the whole prompt compared in the admitting pool, and the store's hot
   and total bytes read against their budgets.

The call's sample runs from just before 1 to the end of the last
`block_until_ready` of 2: the part of a turn's time to first token that
is the cache's.  3 to 5 are the same one client thread's and off the
call's clock: they lower `goodput`, not `call_p50`.  `goodput` counts a
restored page's bytes once, when it is in the admitting pool:
`bytes_per_call` is the window's restored bytes over its turns, so that
the harness's product of the two is the window's restored bytes.

Programs run on 16, 4 or 1 pages (`pieces`), so that a run cut
anywhere by a dropped block compiles nothing inside the window (five
sizes, 16 down to 1 by halves, were two minutes of compiles in a
checkout's first run; the common remainders of 8, 10 and 12 pages are
two to four launches of 0.4 ms this way); every size is warmed in
set-up, then `warm_sessions` sessions run untimed, so that the store is
past its hot budget when the window opens.

The reference (benchmark/reference_kv_prefix.py) says the order of
turns, every page's content and checksum, and, replayed over this run's
publishes and fetches, the depth each turn may restore.
"""

from __future__ import annotations

import random
import shutil
import time

from benchmark import counters, reference_kv, reference_kv_prefix as ref
from benchmark.drivers.kv_pull import SHM_FREE_NEEDED
from benchmark.evidence import Evidence
from benchmark.payload import SendOnce


class _TimedRegistry:
    """The registry client the program is given, its prefix round trip
    recorded as the interval `put`: it lies inside `publish`."""

    def __init__(self, real, spans):
        self._real, self._spans = real, spans

    def put_prefix_many(self, metas, lease_ms: int = 0):
        t0 = time.perf_counter()
        try:
            return self._real.put_prefix_many(metas, lease_ms=lease_ms)
        finally:
            self._spans.add("put", t0, time.perf_counter())

    def close(self) -> None:
        self._real.close()


def geometry(cfg: dict, mix: dict) -> dict:
    """The cell's sizes from the configuration's widths and the mix's
    counts, and a refusal where the mix's own sizes say otherwise."""
    layers, tokens = int(mix["page_layers"]), int(mix["page_tokens"])
    width = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    g = {"page_shape": (layers, tokens, width),
         "block_bytes": layers * tokens * width * 2,
         "window": int(mix["fetch_window_pages"]),
         "hot_bytes": int(mix.get("prefix_hot_bytes",
                                  cfg["prefix_hot_bytes"])),
         "store_bytes": int(mix.get("store_bytes", cfg["store_bytes"])),
         "longest_prompt": (int(mix["system_pages"])
                            + max(mix["doc_pages"])
                            + int(mix["turn_pages"])
                            * (int(mix["turns"]) - 1))}
    if (g["block_bytes"] != int(mix["block_bytes"]) or tokens % 2
            or layers > int(cfg["num_hidden_layers"])
            or g["window"] not in (1, 4, 16, 64)
            or g["longest_prompt"] > int(mix["pool_pages"])
            or g["hot_bytes"] >= g["store_bytes"]):
        raise ValueError(
            f"the mix and the configuration's widths disagree: a block of "
            f"{g['block_bytes']} B against the mix's {mix['block_bytes']}, "
            f"a window of {g['window']} pages (a power of four), a prompt "
            f"of up to {g['longest_prompt']} pages in a pool of "
            f"{mix['pool_pages']}, hot {g['hot_bytes']} of "
            f"{g['store_bytes']} B")
    return g


def sizes(largest: int) -> list[int]:
    """The sizes the programs are compiled for: `largest`, `largest`/4,
    ... 1 pages."""
    return [largest // 4 ** k for k in range(8) if largest // 4 ** k]


def pieces(n: int, largest: int) -> list[int]:
    """`n` pages as runs of those sizes, the largest first."""
    out = []
    for size in sizes(largest):
        out += [size] * (n // size)
        n %= size
    return out


def run(ctx) -> Evidence:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.models import kv_pool
    from brpc_tpu.rpc import (Channel, RmaBuffer, Server, _lib, get_flag, kv,
                              set_flag, zerocopy)

    # A program from before PR 37 has the prefix plane but not these
    # entries: it is refused here, before anything is set up (found
    # later, at the first prefill, the exception met a transfer under
    # way and the interpreter's exit aborted).
    missing = [name for owner, name in (
        (kv, "publish_prefix_run"), (kv.KvClient, "fetch_prefix_blocks"),
        (kv.KvRegistryClient, "put_prefix_many"))
        if not hasattr(owner, name)]
    if missing:
        raise SystemExit(f"{ctx.cell.name} needs {', '.join(missing)} of "
                         "brpc_tpu.rpc.kv, which this program lacks")
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    g = geometry(cfg, mix)
    page_shape, block_bytes = g["page_shape"], g["block_bytes"]
    window, pool_pages = g["window"], int(mix["pool_pages"])
    page_tokens, vocab = int(mix["page_tokens"]), int(cfg["vocab_size"])
    lease_ms = int(cfg["lease_ms"])
    timeout_ms = int(cfg["call_timeout_ms"])
    words_per_page = page_shape[0] * page_shape[1] * page_shape[2] // 2
    total_blocks = ref.blocks_of(g["store_bytes"], block_bytes)
    hot_blocks = ref.blocks_of(g["hot_bytes"], block_bytes)
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
    def bm_kvp_produce(producing, admitting, from_slots, to_slots, base,
                       consts):
        pages = ref.next_pages(base, consts)
        return (kv_pool.kv_write_pages(producing, from_slots, pages),
                kv_pool.kv_write_pages(admitting, to_slots, pages))

    def bm_kvp_verify(bad, pool, slots, base, consts):
        differ = jnp.any(kv_pool.kv_read_pages(pool, slots)
                         != ref.next_pages(base, consts), axis=(1, 2, 3))
        return bad + jnp.sum(differ, dtype=jnp.uint32)

    def bm_kvp_pool_checksums(pool):
        return jax.lax.map(reference_kv.page_checksum, pool)

    produce = jax.jit(bm_kvp_produce, donate_argnums=(0, 1))
    verify = jax.jit(bm_kvp_verify)
    pool_checksums = jax.jit(bm_kvp_pool_checksums)

    def slot_array(slots):
        # A host array: the program takes it as an argument, where a
        # device array made here would cost a launch of its own.
        return np.asarray(slots, dtype=np.int32)

    def const_array(page_ids):
        return np.asarray([ref.page_const(ctx.seed, owner, index)
                           for owner, index in page_ids], dtype=np.uint32)

    t_setup = [now()]      # the ends of set-up's phases, for the notes
    with jax.default_device(device):
        pools = {"producing": kv_pool.seeded_pool(ctx.seed, pool_pages,
                                                  *page_shape),
                 "admitting": kv_pool.seeded_pool(ctx.seed + 1, pool_pages,
                                                  *page_shape)}
        base = kv_pool.read_page(pools["producing"], 0)
        sums = ref.PoolSums(
            ctx.seed, int(reference_kv.page_checksum(base)), words_per_page,
            *(jax.device_get(pool_checksums(pools[name]))
              for name in ("producing", "admitting")))
    # The base page and the tally are put on the device by name: a pool
    # made under `default_device` is not committed to it, the first
    # produce run makes both pools' successors so, and a program that
    # met both kinds of argument would compile twice, the second time
    # wherever its size first came up again (call A's set-up was 25
    # programs compiled twice).
    base, bad = jax.device_put((np.asarray(base), np.uint32(0)), device)
    jax.block_until_ready((pools, base, bad))

    kv.reset()     # this process's store and registry are this run's
    budget_flags = {"trpc_kv_prefix_hot_bytes": g["hot_bytes"],
                    "trpc_kv_store_bytes": g["store_bytes"]}
    flags_before = {name: get_flag(name) for name in budget_flags}
    for name, value in budget_flags.items():
        set_flag(name, str(value))
    srv = Server()
    srv.enable_kv_store()
    srv.enable_kv_registry()
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    land = RmaBuffer(window * block_bytes)
    landing = np.frombuffer(land.view, dtype=np.uint16).reshape(
        (window,) + page_shape)
    reg = cli = None
    try:
        reg = _TimedRegistry(
            kv.KvRegistryClient(Channel(addr, timeout_ms=timeout_ms),
                                owns_channel=True), spans)
        cli = kv.KvClient(addr, timeout_ms=timeout_ms,
                          use_shm=cfg["channel"]["use_shm"])

        guard = SendOnce()
        rng = random.Random(ctx.seed ^ 0x5EED)
        # The band's lower edge: the model with the total budget cut by
        # one window of blocks (reference_kv_prefix.depth_band says why).
        model = ref.StoreModel(total_blocks - window, hot_blocks)
        finished: list[tuple[float, float, int]] = []  # end, seconds, pages
        faults = {"turns_refused": 0, "runs_over_published": 0,
                  "runs_under_reference": 0, "runs_with_a_hole": 0,
                  "budget_passed": 0}
        page_counts = {"asked": 0, "matched": 0, "restored": 0,
                       "prefilled": 0, "renewed": 0}
        in_trace = {"produce_runs": 0, "pages_produced": 0,
                    "pages_read": 0, "pages_written": 0}
        tracing = False
        turns_run = 0

        def put_and_write(rows, slots) -> None:
            """What landed in `rows` of the landing area goes to `slots`
            of the admitting pool, in pieces of the compiled sizes."""
            cut, at = [], 0
            for n in pieces(len(slots), window):
                cut.append((at, at + n))
                at += n
            with spans.span("h2d"):
                back = jax.block_until_ready(jax.device_put(
                    [rows[a:b] for a, b in cut], device))
            with spans.span("write"):
                for (a, b), pages in zip(cut, back):
                    pools["admitting"] = kv_pool.write_pages(
                        pools["admitting"], slot_array(slots[a:b]), pages)
                jax.block_until_ready(pools["admitting"])
            if tracing:
                in_trace["pages_written"] += len(slots)

        def compare(slots, page_ids) -> None:
            nonlocal bad
            at = 0
            with spans.span("verify"):
                for n in pieces(len(slots), window):
                    bad = verify(bad, pools["admitting"],
                                 slot_array(slots[at:at + n]), base,
                                 const_array(page_ids[at:at + n]))
                    at += n

        def prefill(keys, toks, first, page_ids, to_slots) -> None:
            """Pages `first`.. of the prompt, which no block restored:
            produced, read back and published, a piece at a time, each
            piece's transfer started before the piece before it is
            waited for and published."""
            started = []       # (first page, its ids, the pending view)

            def publish(at, ids, read, view) -> None:
                staged = isinstance(view, zerocopy.PendingView)
                with spans.span("d2h_wait"):
                    if staged:
                        view.resolve()
                with spans.span("publish"):
                    out = kv.publish_prefix_run(
                        keys[at:at + len(ids)], at,
                        view if staged else read,
                        [toks[(at + j) * page_tokens:
                              (at + j + 1) * page_tokens]
                         for j in range(len(ids))],
                        lease_ms=lease_ms, node=addr, registry=reg)
                for block, (_, fresh) in zip(ids, out):
                    model.publish(block)
                    page_counts["renewed"] += not fresh

            at = first
            for n in pieces(len(page_ids) - first, window):
                ids = page_ids[at:at + n]
                from_slots = rng.sample(range(pool_pages), n)
                with spans.span("produce"):
                    pools["producing"], pools["admitting"] = produce(
                        pools["producing"], pools["admitting"],
                        slot_array(from_slots),
                        slot_array(to_slots[at:at + n]), base,
                        const_array(ids))
                sums.write("producing", from_slots, ids)
                sums.write("admitting", to_slots[at:at + n], ids)
                with spans.span("read"):
                    read = kv_pool.read_pages(pools["producing"],
                                              slot_array(from_slots))
                guard.claim(read)
                with spans.span("d2h"):
                    view = zerocopy.host_view(read)[0]
                if tracing:
                    in_trace["produce_runs"] += 1
                    in_trace["pages_produced"] += n
                    in_trace["pages_read"] += n
                started.append((at, ids, read, view))
                if len(started) > 1:
                    publish(*started.pop(0))
                at += n
            while started:
                publish(*started.pop(0))
            page_counts["prefilled"] += len(page_ids) - first

        def one_turn(turn) -> None:
            nonlocal turns_run
            turns_run += 1
            page_ids = turn.page_ids()
            toks = ref.tokens(ctx.seed, turn, page_tokens, vocab)
            to_slots = rng.sample(range(pool_pages), len(page_ids))
            at_least, at_most = ref.depth_band(model, page_ids)
            keys = kv.prefix_chain(toks, page_tokens)
            t0 = now()
            try:
                with spans.span("match"):
                    groups = cli.match_prefix(toks, page_tokens)
                restored = 0
                while restored < len(groups):
                    part = groups[restored:restored + window]
                    with spans.span("fetch"):
                        blocks = cli.fetch_prefix_blocks(
                            part, landing=landing, window=window)
                    for block in page_ids[restored:restored + len(part)]:
                        model.fetch(block)
                    if blocks:
                        put_and_write(
                            landing[:len(blocks)],
                            to_slots[restored:restored + len(blocks)])
                    restored += len(blocks)
                    if len(blocks) < len(part):
                        break
            except kv.RpcError as e:
                print(f"# turn {turns_run} refused: {e}", flush=True)
                faults["turns_refused"] += 1
                return
            t1 = now()
            finished.append((t1, t1 - t0, restored))
            sums.write("admitting", to_slots[:restored],
                       page_ids[:restored])
            compare(to_slots[:restored], page_ids[:restored])
            if restored < len(page_ids):
                prefill(keys, toks, restored, page_ids, to_slots)
            compare(to_slots[restored:], page_ids[restored:])
            page_counts["asked"] += len(page_ids)
            page_counts["matched"] += len(groups)
            page_counts["restored"] += restored
            faults["runs_over_published"] += restored > at_most
            faults["runs_under_reference"] += restored < at_least
            # Contiguous from block 0: the i-th block handed out is the
            # one recorded at depth i of this prompt's chain.
            faults["runs_with_a_hole"] += any(
                group[0].depth != i or group[0].key != keys[i]
                for i, group in enumerate(groups[:restored]))
            hot = kv.prefix_hot_bytes()
            total = hot + kv.prefix_cold_bytes() + kv.store_bytes_used()
            faults["budget_passed"] += (hot > g["hot_bytes"]
                                        or total > g["store_bytes"])

        # ---- every size of every program, then the untimed sessions ----
        # (The first produce run meets the pools as `seeded_pool` made
        # them and commits their successors: the largest size runs twice.)
        t_setup.append(now())
        for size in [window] + sizes(window):
            ids = [(ref.WARM_UP, size + j) for j in range(size)]
            from_slots = rng.sample(range(pool_pages), size)
            to_slots = rng.sample(range(pool_pages), size)
            pools["producing"], pools["admitting"] = produce(
                pools["producing"], pools["admitting"],
                slot_array(from_slots), slot_array(to_slots), base,
                const_array(ids))
            sums.write("producing", from_slots, ids)
            sums.write("admitting", to_slots, ids)
            read = kv_pool.read_pages(pools["producing"],
                                      slot_array(from_slots))
            landing[:size] = zerocopy.host_bytes(read)[0].view(
                np.uint16).reshape((size,) + page_shape)
            to_slots = rng.sample(range(pool_pages), size)
            put_and_write(landing[:size], to_slots)
            sums.write("admitting", to_slots, ids)
            compare(to_slots, ids)
        t_setup.append(now())
        order = ref.turns(ctx.seed, mix)
        warm_sessions = int(mix["warm_sessions"])
        turn = next(order)
        while turn.session < warm_sessions:
            one_turn(turn)
            turn = next(order)
        t_setup.append(now())
        before = counters.read_native()
        compiles_before = ctx.compiles.count
        t_open = now()
        deadline = t_open + ctx.seconds
        trace_at = deadline - min(float(mix["trace_seconds"]), ctx.seconds)
        traced_from = None
        while True:
            one_turn(turn)
            turn = next(order)
            t = now()
            if t >= deadline:
                t_close = t
                break
            if ctx.trace and traced_from is None and t >= trace_at:
                ctx.start_trace()
                traced_from = now()
                tracing = True
        compiles_in_window = ctx.compiles.count - compiles_before
        after = counters.read_native()
        traced = None
        if traced_from is not None:
            ctx.stop_trace()
            traced = (traced_from, t_close)
        mismatched = int(bad)
        transports = cli.transports()

        # ---- both pools against the reference ---------------------------
        slots_differ = sum(
            int(got) != want
            for name in ("producing", "admitting")
            for got, want in zip(
                jax.device_get(pool_checksums(pools[name])),
                sums.sums[name]))
    finally:
        if cli is not None:
            cli.close()
        if reg is not None:
            reg.close()
        srv.stop()
        land.free()
        kv.reset()
        for name, value in flags_before.items():
            set_flag(name, value)

    counted = [(end, s, n) for end, s, n in finished
               if t_open < end <= t_close]
    delta = counters.delta(before, after)
    served = delta.get("kv_prefix_fetch_total", 0.0)
    identities = {
        "served_not_hot_plus_cold": int(
            delta.get("kv_prefix_hot_hits", 0.0)
            + delta.get("kv_prefix_cold_hits", 0.0) != served),
        "promotes_over_cold_hits": int(
            delta.get("kv_prefix_promote", 0.0)
            > delta.get("kv_prefix_cold_hits", 0.0)),
        "pages_not_restored_plus_prefilled": int(
            page_counts["restored"] + page_counts["prefilled"]
            != page_counts["asked"]),
    }
    attempted = (sum(1 for end, _, _ in finished if end > t_open)
                 + faults["turns_refused"])
    failed = (mismatched + slots_differ + sum(faults.values())
              + sum(identities.values()))
    transport = transports.get(addr, "")
    restored_bytes = sum(n for _, _, n in counted) * block_bytes
    yardstick = sum(spans.total(n, t_open, t_close)
                    for n in ("produce", "verify"))
    checked = {"pages_mismatched_on_device": mismatched,
               "pool_slots_differing_from_reference": slots_differ,
               **faults, **identities}
    return Evidence(
        t_open=t_open, t_close=t_close,
        call_s=[s for _, s, _ in counted],
        call_end=[end for end, _, _ in counted],
        # The window's restored bytes over its turns: the harness counts
        # bytes_per_call once a call, and a turn restores 8 to 108 pages.
        bytes_per_call=restored_bytes / max(1, len(counted)),
        attempted=attempted, failed=failed,
        correct=failed == 0 and transport == cfg["transport"],
        compiles_in_window=compiles_in_window, spans=spans,
        counters=delta, traced=traced,
        notes={
            "transport": transport,
            "transport_expected": cfg["transport"],
            # Each number `correct` rests on beside what it must be: the
            # harness prints `<name>_differs` beside its limit 0.
            **checked, **{f"{name}_expected": 0 for name in checked},
            "native_build": built,
            "seed_checksum": sums.base_sum,
            "setup_phases_s": dict(zip(
                ("pools_and_server", "every_size_compiled", "warm_sessions"),
                (b - a for a, b in zip(t_setup, t_setup[1:])))),
            "turns_run": turns_run,
            "turns_in_window": len(counted),
            "block_bytes": block_bytes,
            "store_blocks": total_blocks, "hot_blocks": hot_blocks,
            "pages": page_counts,
            "restored_bytes_in_window": restored_bytes,
            "traced": in_trace,
            "reference_model": dict(model.counts),
            "hit_share_lru_model": ref.self_driven_hit_share(
                ctx.seed, mix, turns_run, total_blocks, hot_blocks, False),
            "hit_share_chain_aware_model": ref.self_driven_hit_share(
                ctx.seed, mix, turns_run, total_blocks, hot_blocks, True),
            "pool_bytes": {name: int(pool.nbytes)
                           for name, pool in pools.items()},
            "yardstick_share_of_window": yardstick / (t_close - t_open),
        })
