"""Driver `kv_prefix_linear`: agent sessions of a hybrid linear-attention
model restore their long context from the content-addressed KV pool as
latent pages plus the recurrent state at the deepest boundary
(brpc_tpu/rpc/kv.py over cpp/net/kvstore.cc: chain keys, `KvReg.Match`
answering the pages up to a state and that state, `Kv.FetchPrefix`, the
two-tier store under its hot and total budgets) into an admitting rank's
pools in HBM, and publish what their turn prefilled.

One process holds the chip, the four pools (brpc_tpu/models/kv_pool.py:
a page pool and a state pool for the rank whose prefill makes new pages
and for the rank that admits a turn), the Server with the store and the
registry, and the admitting side's KvClient; the configuration file says
what that stands for.  The traffic is a fixed replay
(benchmark/reference_kv_prefix_linear.py::turns): the sessions, their
lengths and their order are the same in every run, the seed decides the
bytes.  One call is one turn of one session:

1. `KvClient.match_prefix(tokens)`: the chain keys of the prompt's full
   pages, one `KvReg.Match`, whose answer ends at the deepest boundary
   at which a live state lies and carries that state's replicas;
2. the pages under it, `fetch_window_pages` at a time:
   `KvClient.fetch_prefix_blocks` into the landing area (a page is under
   `trpc_stripe_threshold` and rides the ring's frames), the state in the
   first round beside them into a registered area of its own (over the
   threshold: one-sided), one `jax.device_put` a window, `write_pages`
   into the session's slots of the admitting page pool; then the
   state's `device_put` and `write_page` into the session's slot of the
   admitting state pool, ended by `block_until_ready`.  A state that
   does not land, or a page under it that does not, ends the hit at the
   previous boundary whose state lands (`fetch_prefix_state`), or at 0;
3. the compare, on the device: every restored page and the state
   against the reference's, folded into one device scalar fetched once,
   after the window;
4. the pages not restored, a window at a time: the produce program (the
   yardstick's stand-in for a prefill) writes them into seeded slots of
   the producing pool and the session's slots of the admitting pool, and
   the state program adds their term to the session's state (the
   stand-in for the KDA layers' prefill); `read_pages` / `read_page` and
   `zerocopy.host_view`; `kv.prefix_publish_state` (the state into the
   store where its transfer landed it) and `kv.publish_prefix_run` (the
   pages, and both records in one `KvReg.PutPrefixMany`);
5. the prompt's new pages and the new state compared in the admitting
   pools, the store's hot and total bytes read against their budgets.

The call's sample runs from just before 1 to the end of 2's last
`block_until_ready`: the part of a turn's time to first token that is the
cache's.  3 to 5 are the same client thread's and off the call's clock.
`goodput` counts a turn's restored bytes, pages and state, once, when
they are in the admitting pools: `bytes_per_call` is the window's
restored bytes over its turns.

Programs run on `fetch_window_pages` pages and on each quarter down to 1
(`kv_prefix.pieces`); every size is warmed in set-up, then each
session's history is prefilled and published from page 0 and one round
runs untimed, so that the window opens with superseded states in the
store.  The window ends at `--seconds`, or earlier where the sessions'
slots of the admitting pool hold no further round (`rounds_left` in the
notes).
"""

from __future__ import annotations

import random
import shutil
import time

from benchmark import counters, reference_kv
from benchmark import reference_kv_prefix as pages_ref
from benchmark import reference_kv_prefix_linear as ref
from benchmark.drivers.kv_pull import SHM_FREE_NEEDED
from benchmark.drivers.kv_prefix import _TimedRegistry, pieces, sizes
from benchmark.evidence import Evidence
from benchmark.payload import SendOnce

STATE_ROW_WORDS = 128     # a state in its pool: rows of 128 2-byte words


def geometry(cfg: dict, mix: dict) -> dict:
    """The cell's sizes from the configuration's widths and the mix's
    counts, and a refusal where the mix's own sizes say otherwise or
    break the rule the hot tier is sized by (`round_fits_hot`).  The mix
    takes the first `page_layers` of the configuration's full-attention
    layers and the first `snapshot_layers` of its KDA layers (all of them
    at the timed size)."""
    lin = cfg["linear_attn_config"]
    layers, tokens = int(mix["page_layers"]), int(mix["page_tokens"])
    width = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    heads, head_dim = int(lin["num_heads"]), int(lin["head_dim"])
    snapshot = (heads * head_dim * head_dim * 4
                + 3 * (int(lin["short_conv_kernel_size"]) - 1)
                * heads * head_dim * 2)
    states = int(mix["snapshot_layers"])
    g = {"page_shape": (layers, tokens, width),
         "page_bytes": layers * tokens * width * 2,
         "state_shape": (states, snapshot // (2 * STATE_ROW_WORDS),
                         STATE_ROW_WORDS),
         "state_bytes": states * snapshot,
         "window": int(mix["fetch_window_pages"]),
         "hot_bytes": int(mix.get("prefix_hot_bytes",
                                  cfg["prefix_hot_bytes"])),
         "store_bytes": int(mix.get("store_bytes", cfg["store_bytes"])),
         "rounds_fit": ref.rounds_fit(mix),
         "per_session": int(mix["pool_pages"]) // int(mix["sessions"])}
    if (g["page_bytes"] != int(mix["page_bytes"])
            or g["state_bytes"] != int(mix["state_bytes"])
            or layers > len(lin["full_attn_layers"])
            or states > len(lin["kda_layers"])
            or tokens % 2 or snapshot % (4 * STATE_ROW_WORDS)
            or g["window"] not in (1, 4, 16, 64)
            or int(mix["turn_pages"]) not in sizes(g["window"])
            or g["rounds_fit"] < 1
            or int(mix["state_slots"]) < int(mix["sessions"]) + 1
            or g["hot_bytes"] >= g["store_bytes"]
            or not ref.round_fits_hot(mix, g["hot_bytes"])):
        raise ValueError(
            f"the mix and the configuration's widths disagree, or the hot "
            f"tier does not hold a round: a page of {g['page_bytes']} B "
            f"against the mix's {mix['page_bytes']}, a state of "
            f"{g['state_bytes']} B against {mix['state_bytes']}, a window "
            f"of {g['window']} pages (a power of four), {g['rounds_fit']} "
            f"rounds in the pool, a round of "
            f"{ref.round_bytes(mix, g['rounds_fit'])} B and hot "
            f"{g['hot_bytes']} of {g['store_bytes']} B")
    return g


def run(ctx) -> Evidence:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.models import kv_pool
    from brpc_tpu.rpc import (Channel, RmaBuffer, Server, _lib, get_flag, kv,
                              set_flag, zerocopy)

    # A program without the state kind of the prefix store is refused
    # here, before anything is set up.
    missing = [name for owner, name in (
        (kv, "prefix_publish_state"), (kv.KvClient, "fetch_prefix_state"),
        (kv, "PrefixMatch")) if not hasattr(owner, name)]
    if missing:
        raise SystemExit(f"{ctx.cell.name} needs {', '.join(missing)} of "
                         "brpc_tpu.rpc.kv, which this program lacks")
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    g = geometry(cfg, mix)
    page_shape, state_shape = g["page_shape"], g["state_shape"]
    page_bytes, state_bytes = g["page_bytes"], g["state_bytes"]
    window, per_session = g["window"], g["per_session"]
    pool_pages, state_slots = int(mix["pool_pages"]), int(mix["state_slots"])
    sessions = int(mix["sessions"])
    page_tokens, vocab = int(mix["page_tokens"]), int(cfg["vocab_size"])
    lease_ms = int(cfg["lease_ms"])
    timeout_ms = int(cfg["call_timeout_ms"])
    words_per_page = page_bytes // 4
    words_per_state = state_bytes // 4
    device = ctx.devices[0]
    spans = ctx.spans
    now = time.perf_counter

    shm_needed = SHM_FREE_NEEDED + g["hot_bytes"]
    shm_free = shutil.disk_usage("/dev/shm").free
    if shm_free < shm_needed:
        raise SystemExit(
            f"/dev/shm has {shm_free} bytes free; the hot tier and the "
            f"transports need {shm_needed}")
    built = _lib.ensure_built()
    _lib.load_library()

    # ---- the yardstick's own programs --------------------------------
    def bm_kvl_produce(producing, admitting, from_slots, to_slots, base,
                       consts):
        pages = pages_ref.next_pages(base, consts)
        return (kv_pool.kv_write_pages(producing, from_slots, pages),
                kv_pool.kv_write_pages(admitting, to_slots, pages))

    def bm_kvl_state_produce(producing_states, admitting_states, out_slot,
                             session_slot, admitting, page_slots):
        # The new pages' term: each page's checksum made odd, summed.
        sums = jax.vmap(reference_kv.page_checksum)(
            kv_pool.kv_read_pages(admitting, page_slots))
        add = jnp.sum(sums | jnp.uint32(1), dtype=jnp.uint32)
        state = pages_ref.next_pages(
            kv_pool.kv_read_page(admitting_states, session_slot),
            add[None])[0]
        return (kv_pool.kv_write_page(producing_states, out_slot, state),
                kv_pool.kv_write_page(admitting_states, session_slot, state))

    def bm_kvl_verify(bad, pool, slots, base, consts):
        differ = jnp.any(kv_pool.kv_read_pages(pool, slots)
                         != pages_ref.next_pages(base, consts), axis=(1, 2, 3))
        return bad + jnp.sum(differ, dtype=jnp.uint32)

    def bm_kvl_state_verify(bad, states, slot, base_state, const):
        differ = jnp.any(kv_pool.kv_read_page(states, slot)
                         != pages_ref.next_pages(base_state, const[None])[0])
        return bad + differ.astype(jnp.uint32)

    def bm_kvl_pool_checksums(pool):
        return jax.lax.map(reference_kv.page_checksum, pool)

    produce = jax.jit(bm_kvl_produce, donate_argnums=(0, 1))
    state_produce = jax.jit(bm_kvl_state_produce, donate_argnums=(0, 1))
    verify = jax.jit(bm_kvl_verify)
    state_verify = jax.jit(bm_kvl_state_verify)
    pool_checksums = jax.jit(bm_kvl_pool_checksums)

    def slot_array(slots):
        # A host array: the program takes it as an argument, where a
        # device array made here would cost a launch of its own.
        return np.asarray(slots, dtype=np.int32)

    def const_array(page_ids):
        return np.asarray([pages_ref.page_const(ctx.seed, owner, index)
                           for owner, index in page_ids], dtype=np.uint32)

    t_setup = [now()]      # the ends of set-up's phases, for the notes
    with jax.default_device(device):
        pools = {
            "producing": kv_pool.seeded_pool(ctx.seed, pool_pages,
                                             *page_shape),
            "admitting": kv_pool.seeded_pool(ctx.seed + 1, pool_pages,
                                             *page_shape),
            "producing_states": kv_pool.seeded_pool(
                ctx.seed + 2, state_slots, *state_shape),
            "admitting_states": kv_pool.seeded_pool(
                ctx.seed + 3, state_slots, *state_shape)}
        base = kv_pool.read_page(pools["producing"], 0)
        base_state = kv_pool.read_page(pools["producing_states"], 0)
        sums = ref.PoolSums(
            ctx.seed, int(reference_kv.page_checksum(base)), words_per_page,
            int(reference_kv.page_checksum(base_state)), words_per_state,
            {name: jax.device_get(pool_checksums(pool))
             for name, pool in pools.items()})
    # Put on the device by name, as kv_prefix does: a pool made under
    # `default_device` is not committed to it, and a program that met
    # both kinds of argument would compile twice.
    base, base_state, bad = jax.device_put(
        (np.asarray(base), np.asarray(base_state), np.uint32(0)), device)
    jax.block_until_ready((pools, base, base_state, bad))

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
    land = RmaBuffer(window * page_bytes)
    landing = np.frombuffer(land.view, dtype=np.uint16).reshape(
        (window,) + page_shape)
    state_land = RmaBuffer(state_bytes)
    state_landing = np.frombuffer(state_land.view, dtype=np.uint16).reshape(
        state_shape)
    reg = cli = None
    try:
        reg = _TimedRegistry(
            kv.KvRegistryClient(Channel(addr, timeout_ms=timeout_ms),
                                owns_channel=True), spans)
        cli = kv.KvClient(addr, timeout_ms=timeout_ms,
                          use_shm=cfg["channel"]["use_shm"])

        guard = SendOnce()
        rng = random.Random(ctx.seed ^ 0x5EED)
        size_of = {"page": page_bytes, "state": state_bytes}
        model = ref.StoreModel(g["store_bytes"], g["hot_bytes"],
                               lambda block: size_of[ref.kind(block)])
        # Every session's prompt tokens, a page at a time as they come.
        tokens = np.zeros((sessions, per_session * page_tokens), np.uint64)
        made = [0] * sessions
        consts = {}        # session -> C(b) of its newest state
        finished: list[tuple[float, float, int]] = []  # end, seconds, bytes
        faults = {"turns_refused": 0, "pages_or_state_differ": 0,
                  "turns_off_the_model": 0,
                  "states_of_another_boundary_or_session": 0,
                  "budget_passed": 0}
        counts = {"pages_asked": 0, "pages_restored": 0,
                  "pages_prefilled": 0, "states_restored": 0, "turns": 0,
                  "fallbacks": 0}
        in_trace = {"produce_runs": 0, "pages_produced": 0,
                    "state_runs": 0, "state_pages": 0, "pages_read": 0,
                    "states_read": 0, "pages_written": 0,
                    "states_written": 0}
        tracing = False

        def session_slots(session, first, n):
            at = session * per_session + first
            return list(range(at, at + n))

        def prompt_tokens(turn):
            s = turn.session
            while made[s] < turn.prompt_pages:
                owner = turn.owner(made[s])
                tokens[s, made[s] * page_tokens:
                       (made[s] + 1) * page_tokens] = pages_ref.page_token_ids(
                    ctx.seed, owner, made[s], page_tokens, vocab)
                made[s] += 1
            return tokens[s, :turn.prompt_pages * page_tokens]

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
                for (a, b), part in zip(cut, back):
                    pools["admitting"] = kv_pool.write_pages(
                        pools["admitting"], slot_array(slots[a:b]), part)
                jax.block_until_ready(pools["admitting"])
            if tracing:
                in_trace["pages_written"] += len(slots)

        def put_and_write_state(session) -> None:
            with spans.span("state_h2d"):
                back = jax.block_until_ready(
                    jax.device_put(state_landing, device))
            with spans.span("write"):
                pools["admitting_states"] = kv_pool.write_page(
                    pools["admitting_states"], session, back)
                jax.block_until_ready(pools["admitting_states"])
            if tracing:
                in_trace["states_written"] += 1

        def compare(slots, page_ids) -> None:
            nonlocal bad
            at = 0
            with spans.span("verify"):
                for n in pieces(len(slots), window):
                    bad = verify(bad, pools["admitting"],
                                 slot_array(slots[at:at + n]), base,
                                 const_array(page_ids[at:at + n]))
                    at += n

        def compare_state(session, const) -> None:
            nonlocal bad
            with spans.span("verify"):
                bad = state_verify(bad, pools["admitting_states"], session,
                                   base_state, np.uint32(const))

        def prefill(turn, toks, first, keys) -> None:
            """Pages `first`.. of the prompt, which no block restored,
            produced and read back a piece at a time, the session's state
            carried over them; then the new state and the pages
            published, each piece's transfer started before the piece
            before it is waited for and published, the last piece with
            the state."""
            s = turn.session
            page_ids = turn.page_ids()
            to_slots = session_slots(s, 0, turn.prompt_pages)
            started = []       # (first page, its ids, read, pending view)

            def publish(at, ids, read, view, state=None) -> None:
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
                        lease_ms=lease_ms, node=addr, registry=reg,
                        state=state)
                for block, _ in zip(ids, out):
                    model.publish(block)

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
                sums.pages("producing", from_slots, ids)
                sums.pages("admitting", to_slots[at:at + n], ids)
                out_slot = rng.randrange(sessions, state_slots)
                with spans.span("state_produce"):
                    (pools["producing_states"],
                     pools["admitting_states"]) = state_produce(
                        pools["producing_states"],
                        pools["admitting_states"], out_slot, s,
                        pools["admitting"], slot_array(to_slots[at:at + n]))
                consts[s] = (consts[s] + ref.state_const(
                    ctx.seed, sums.base_sum, words_per_page, ids)) & ref.MASK
                sums.state("producing_states", out_slot, consts[s])
                sums.state("admitting_states", s, consts[s])
                with spans.span("read"):
                    read = kv_pool.read_pages(pools["producing"],
                                              slot_array(from_slots))
                guard.claim(read)
                with spans.span("d2h"):
                    view = zerocopy.host_view(read)[0]
                if tracing:
                    in_trace["produce_runs"] += 1
                    in_trace["pages_produced"] += n
                    in_trace["state_runs"] += 1
                    in_trace["state_pages"] += n
                    in_trace["pages_read"] += n
                started.append((at, ids, read, view))
                if len(started) > 1:
                    publish(*started.pop(0))
                at += n
            with spans.span("read"):
                state_read = kv_pool.read_page(pools["producing_states"],
                                               out_slot)
            guard.claim(state_read)
            with spans.span("d2h"):
                state_view = zerocopy.host_view(state_read)[0]
            if tracing:
                in_trace["states_read"] += 1
            # The state at the prompt's end, then the last piece with it.
            boundary = turn.prompt_pages
            staged = isinstance(state_view, zerocopy.PendingView)
            with spans.span("state_publish"):
                state, _ = kv.prefix_publish_state(
                    keys[boundary - 1], boundary,
                    state_view if staged else state_read,
                    toks[(boundary - 1) * page_tokens:],
                    lease_ms=lease_ms, node=addr)
            model.publish(turn.state_id(boundary))
            publish(*started.pop(0), state=state)
            counts["pages_prefilled"] += len(page_ids) - first

        def restore(turn, toks, found) -> tuple[int, int]:
            """Steps 1-2 after the match: (pages restored, the boundary
            of the state restored with them)."""
            s = turn.session
            target = found.boundary if found.state else 0
            got_state = None
            restored = 0
            while restored < target:
                part = found[restored:min(restored + window, target)]
                first = restored == 0
                t0 = now()
                with spans.span("fetch"):
                    blocks = cli.fetch_prefix_blocks(
                        part, landing=landing, window=window,
                        state=found.state if first else None,
                        state_landing=state_landing)
                if first:
                    spans.add("state_fetch", t0,
                              blocks.state_landed_at or now())
                    got_state = blocks.state
                if blocks:
                    put_and_write(landing[:len(blocks)],
                                  session_slots(s, restored, len(blocks)))
                restored += len(blocks)
                if len(blocks) < len(part) or got_state is None:
                    break
            if target and (got_state is None or restored < target):
                # The hit ends at the previous boundary whose state
                # lands, under the pages that did.
                counts["fallbacks"] += 1
                below = (min(restored, target - 1) if got_state is None
                         else restored)
                target, got_state = cli.fetch_prefix_state(
                    toks, page_tokens, below, state_landing)
                restored = min(restored, target)
            if got_state is not None:
                put_and_write_state(s)
                return restored, target
            return restored, 0

        def one_turn(turn) -> None:
            s = turn.session
            counts["turns"] += 1
            page_ids = turn.page_ids()
            toks = prompt_tokens(turn)
            keys = kv.prefix_chain(toks, page_tokens)
            # What the reference's store gives this turn, the model's
            # touches the client's.
            want = model.restore(turn, window)
            t0 = now()
            try:
                with spans.span("match"):
                    found = cli.match_prefix(toks, page_tokens)
                # The state answered is this prompt's, at its boundary.
                faults["states_of_another_boundary_or_session"] += any(
                    r.key != keys[found.boundary - 1]
                    or r.depth != found.boundary for r in found.state)
                restored, boundary = restore(turn, toks, found)
            except kv.RpcError as e:
                print(f"# turn {counts['turns']} refused: {e}", flush=True)
                faults["turns_refused"] += 1
                return
            t1 = now()
            if restored != boundary:
                # Pages without their state, or a state without its pages.
                faults["turns_refused"] += 1
                return
            finished.append((t1, t1 - t0,
                             restored * page_bytes
                             + (state_bytes if boundary else 0)))
            faults["turns_off_the_model"] += (restored, boundary) != want
            slots = session_slots(s, 0, turn.prompt_pages)
            sums.pages("admitting", slots[:restored], page_ids[:restored])
            compare(slots[:restored], page_ids[:restored])
            consts[s] = ref.state_const(ctx.seed, sums.base_sum,
                                        words_per_page, page_ids[:boundary])
            if boundary:
                sums.state("admitting_states", s, consts[s])
                compare_state(s, consts[s])
            else:
                # Nothing restored: the state starts from the base state.
                pools["admitting_states"] = kv_pool.write_page(
                    pools["admitting_states"], s, base_state)
                sums.state("admitting_states", s, 0)
            counts["states_restored"] += bool(boundary)
            prefill(turn, toks, restored, keys)
            compare(slots[restored:], page_ids[restored:])
            compare_state(s, consts[s])
            counts["pages_asked"] += len(page_ids)
            counts["pages_restored"] += restored
            hot = kv.prefix_hot_bytes()
            total = hot + kv.prefix_cold_bytes() + kv.store_bytes_used()
            faults["budget_passed"] += (hot > g["hot_bytes"]
                                        or total > g["store_bytes"])

        # ---- every size of every program, then the sessions' histories
        # and one untimed round ----
        t_setup.append(now())
        for size in [window] + sizes(window):
            ids = [(pages_ref.WARM_UP, size + j) for j in range(size)]
            from_slots = rng.sample(range(pool_pages), size)
            to_slots = rng.sample(range(pool_pages), size)
            pools["producing"], pools["admitting"] = produce(
                pools["producing"], pools["admitting"],
                slot_array(from_slots), slot_array(to_slots), base,
                const_array(ids))
            sums.pages("producing", from_slots, ids)
            sums.pages("admitting", to_slots, ids)
            out_slot = rng.randrange(sessions, state_slots)
            pools["producing_states"], pools["admitting_states"] = (
                state_produce(pools["producing_states"],
                              pools["admitting_states"], out_slot, 0,
                              pools["admitting"], slot_array(to_slots)))
            read = kv_pool.read_pages(pools["producing"],
                                      slot_array(from_slots))
            landing[:size] = zerocopy.host_bytes(read)[0].view(
                np.uint16).reshape((size,) + page_shape)
            to_slots = rng.sample(range(pool_pages), size)
            put_and_write(landing[:size], to_slots)
            sums.pages("admitting", to_slots, ids)
            compare(to_slots, ids)
        state_landing[:] = zerocopy.host_bytes(kv_pool.read_page(
            pools["producing_states"], out_slot))[0].view(
            np.uint16).reshape(state_shape)
        put_and_write_state(0)
        # The warm-up's states are no session's: every slot is set anew.
        for slot in range(state_slots):
            pools["admitting_states"] = kv_pool.write_page(
                pools["admitting_states"], slot, base_state)
            pools["producing_states"] = kv_pool.write_page(
                pools["producing_states"], slot, base_state)
            sums.state("admitting_states", slot, 0)
            sums.state("producing_states", slot, 0)
        compare_state(0, 0)
        t_setup.append(now())
        for s in range(sessions):
            turn = ref.history(mix, s)
            toks = prompt_tokens(turn)
            consts[s] = 0
            prefill(turn, toks, 0, kv.prefix_chain(toks, page_tokens))
            compare(session_slots(s, 0, turn.prompt_pages), turn.page_ids())
            compare_state(s, consts[s])
        t_setup.append(now())
        order = ref.turns(mix)
        turn = next(order)
        while turn.round < int(mix["warm_rounds"]):
            one_turn(turn)
            turn = next(order)
        t_setup.append(now())
        before = counters.read_native()
        model_before = dict(model.counts)
        compiles_before = ctx.compiles.count
        t_open = now()
        deadline = t_open + ctx.seconds
        trace_at = deadline - min(float(mix["trace_seconds"]), ctx.seconds)
        traced_from = None
        while True:
            one_turn(turn)
            turn = next(order)
            t = now()
            if t >= deadline or turn.round >= g["rounds_fit"]:
                t_close = t
                break
            if ctx.trace and traced_from is None and t >= trace_at:
                ctx.start_trace()
                traced_from = now()
                tracing = True
        compiles_in_window = ctx.compiles.count - compiles_before
        after = counters.read_native()
        model_in_window = {k: v - model_before.get(k, 0)
                           for k, v in model.counts.items()}
        traced = None
        if traced_from is not None:
            ctx.stop_trace()
            traced = (traced_from, t_close)
        mismatched = int(bad)
        transports = cli.transports()

        # ---- all four pools against the reference ----------------------
        slots_differ = sum(
            int(got) != want
            for name in pools
            for got, want in zip(jax.device_get(pool_checksums(pools[name])),
                                 sums.sums[name]))
    finally:
        if cli is not None:
            cli.close()
        if reg is not None:
            reg.close()
        srv.stop()
        land.free()
        state_land.free()
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
        "state_matches_over_matches": int(
            delta.get("kv_prefix_match_states", 0.0)
            > delta.get("kv_prefix_match_total", 0.0)),
    }
    faults["pages_or_state_differ"] = mismatched
    attempted = (sum(1 for end, _, _ in finished if end > t_open)
                 + faults["turns_refused"])
    failed = slots_differ + sum(faults.values()) + sum(identities.values())
    transport = transports.get(addr, "")
    restored_bytes = sum(n for _, _, n in counted)
    yardstick = sum(spans.total(n, t_open, t_close)
                    for n in ("produce", "state_produce", "verify"))
    checked = {**faults, "pool_slots_differing_from_reference": slots_differ,
               **identities}
    return Evidence(
        t_open=t_open, t_close=t_close,
        call_s=[s for _, s, _ in counted],
        call_end=[end for end, _, _ in counted],
        # The window's restored bytes over its turns: the harness counts
        # bytes_per_call once a call, and a turn restores 1,000 to 1,150
        # pages and a state.
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
                ("pools_and_server", "every_size_compiled", "histories",
                 "warm_rounds"),
                (b - a for a, b in zip(t_setup, t_setup[1:])))),
            "turns_in_window": len(counted),
            "rounds_left": g["rounds_fit"] - turn.round,
            "page_bytes": page_bytes, "state_bytes": state_bytes,
            "counts": counts,
            "restored_bytes_in_window": restored_bytes,
            "traced": in_trace,
            # The window's demotes and drops by the reference's model,
            # beside the store's counters of the same names.
            "reference_model_in_window": model_in_window,
            "pool_bytes": {name: int(pool.nbytes)
                           for name, pool in pools.items()},
            "yardstick_share_of_window": yardstick / (t_close - t_open),
        })
