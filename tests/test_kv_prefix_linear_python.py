"""The prefix store's state kind through its Python surface: a hybrid
cache (latent pages beside the recurrent state of linear-attention
layers) restored as the pages up to a boundary and the state taken
there.  `KvReg.Match` answers the deepest live boundary with its pages,
never a state without its pages or past the matched run, never a state
of another boundary or prompt; a state that does not come ends the hit at
the previous boundary whose state does; states move through the two
tiers in the one touch order, by the code that moves pages; the counters
count; and the store against the plain reference's model, turn by turn,
over the benchmark's replay at toy sizes."""

import numpy as np
import pytest

from benchmark import reference_kv_prefix as pages_ref
from benchmark import reference_kv_prefix_linear as ref
from brpc_tpu.rpc import RmaBuffer, kv, observe, set_flag
from test_kv_prefix_lanes import GOLDEN, golden_page, golden_tokens
from test_kv_prefix_python import counted, delta, hub  # noqa: F401

PAGE = 36864             # a toy page
STATE = 3 << 20          # a toy state, over trpc_stripe_threshold
BT = 4                   # tokens a page


def page_bytes(seed: int, nbytes: int = PAGE) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, nbytes,
                                                dtype=np.uint8)


def page_of(key) -> np.ndarray:
    """A page's bytes, a function of its chain key: a prompt that shares
    a page with another publishes the same bytes."""
    return page_bytes(key[1] & 0xFFFFFFFF)


def state_of(key) -> np.ndarray:
    """The state's bytes after the page of chain key `key`."""
    return page_bytes(key[0] & 0xFFFFFFFF, STATE)


def publish(addr, reg, tokens, first: int, pages: int,
            states=()) -> dict:
    """Publishes pages `first` .. `first + pages` of the prompt `tokens`
    and the states at `states` (boundaries inside that range), each
    state with the run of pages that ends at it; returns the states'
    bytes by boundary."""
    keys = kv.prefix_chain(tokens, BT)
    out = {}
    at = first
    for end in sorted(states) + [first + pages]:
        if end <= at:
            continue
        state = None
        if end in states:
            out[end] = state_of(keys[end - 1])
            state, _ = kv.prefix_publish_state(
                keys[end - 1], end, out[end], tokens[(end - 1) * BT:end * BT],
                lease_ms=60000, node=addr)
        kv.publish_prefix_run(
            keys[at:end], at,
            np.concatenate([page_of(keys[i]) for i in range(at, end)]),
            [tokens[i * BT:(i + 1) * BT] for i in range(at, end)],
            lease_ms=60000, node=addr, registry=reg, state=state)
        at = end
    return out


def test_match_answers_the_deepest_live_boundary_with_its_pages(hub):
    addr, reg, cli = hub
    tokens = np.arange(14 * BT, dtype=np.uint64) + 7
    states = publish(addr, reg, tokens, 0, 12, states=(4, 8))
    before = counted()
    found = cli.match_prefix(tokens, BT)
    # 12 pages matched, the deepest state at 8: the answer ends there.
    assert len(found) == found.boundary == 8
    assert [g[0].depth for g in found] == list(range(8))
    keys = kv.prefix_chain(tokens, BT)
    assert [(r.key, r.depth, r.flags) for r in found.state] == [
        (keys[7], 8, kv.PREFIX_STATE)]
    assert not any(r.flags & kv.PREFIX_STATE for g in found for r in g)
    landed = cli.fetch_prefix_blocks(found, window=3, state=found.state)
    assert len(landed) == 8 and landed.state is not None
    assert landed.state.tobytes() == states[8].tobytes()
    assert landed.state_landed_at is not None
    for key, block in zip(keys, landed):
        assert block.tobytes() == page_of(key).tobytes()
    got = delta(before)
    assert (got["kv_prefix_match_states"],
            got["kv_prefix_match_states_shallow"]) == (1, 1)
    assert got["kv_prefix_match_blocks"] == 8
    # With the pages past 8 cut off, the state at 8 is at the run's end.
    before = counted()
    assert cli.match_prefix(tokens[:8 * BT], BT).boundary == 8
    assert delta(before).get("kv_prefix_match_states_shallow", 0) == 0


def test_a_state_is_never_answered_without_its_pages_or_past_the_run(hub):
    addr, reg, cli = hub
    tokens = np.arange(10 * BT, dtype=np.uint64) + 100
    keys = kv.prefix_chain(tokens, BT)
    publish(addr, reg, tokens, 0, 4, states=(2,))
    # A state at 6 whose pages 4 and 5 were never published.
    state, _ = kv.prefix_publish_state(keys[5], 6, page_bytes(26, STATE),
                                       tokens[20:24], node=addr)
    reg.put_prefix_many([state], lease_ms=60000)
    found = cli.match_prefix(tokens, BT)
    assert found.boundary == 2 and len(found) == 2
    # A state with no page under it is no hit at all.
    other = np.arange(6 * BT, dtype=np.uint64) + 9000
    okeys = kv.prefix_chain(other, BT)
    lone, _ = kv.prefix_publish_state(okeys[2], 3, page_bytes(27, STATE),
                                      other[8:12], node=addr)
    reg.put_prefix_many([lone], lease_ms=60000)
    found = cli.match_prefix(other, BT)
    assert list(found) == [] and found.state == [] and found.boundary == 0
    # Pages with no state anywhere: a paged cache's answer, as before.
    plain = np.arange(5 * BT, dtype=np.uint64) + 5000
    publish(addr, reg, plain, 0, 5)
    found = cli.match_prefix(plain, BT)
    assert len(found) == 5 and found.boundary == 0 and found.state == []
    # The state a run records is the one at its end, of its own chain.
    with pytest.raises(ValueError, match="not the state of the run's end"):
        kv.publish_prefix_run(
            okeys[3:5], 3, np.concatenate([page_bytes(1), page_bytes(2)]),
            [other[12:16], other[16:20]], node=addr, registry=reg,
            state=lone)


def test_a_dropped_newest_state_falls_back_to_the_previous_boundary(hub):
    addr, reg, cli = hub
    tokens = np.arange(13 * BT, dtype=np.uint64) + 300
    states = publish(addr, reg, tokens, 0, 12, states=(4, 8, 12))
    found = cli.match_prefix(tokens, BT)
    assert found.boundary == 12
    kv.prefix_withdraw(found.state[0].hash)
    # The registry still answers 12; its fetch answers kv-stale, so the
    # state and its pages are not handed out.
    again = cli.match_prefix(tokens, BT)
    assert again.boundary == 12
    got = cli.fetch_prefix_blocks(again, window=4, state=again.state)
    assert got.state is None and len(got) == 12
    boundary, state = cli.fetch_prefix_state(tokens, BT, 11)
    assert boundary == 8 and state.tobytes() == states[8].tobytes()
    # The state at 8 dropped too: 4; all of them: 0.
    kv.prefix_withdraw(cli.match_prefix(tokens[:11 * BT], BT).state[0].hash)
    assert cli.fetch_prefix_state(tokens, BT, 11)[0] == 4
    kv.prefix_withdraw(cli.match_prefix(tokens[:7 * BT], BT).state[0].hash)
    assert cli.fetch_prefix_state(tokens, BT, 11) == (0, None)
    # A page under the state that no replica serves cuts the run: the
    # state goes with it.
    fresh = np.arange(9 * BT, dtype=np.uint64) + 700
    publish(addr, reg, fresh, 0, 8, states=(4, 8))
    hole = cli.match_prefix(fresh, BT)
    kv.prefix_withdraw(hole[5][0].hash)
    got = cli.fetch_prefix_blocks(hole, window=8, state=hole.state)
    assert len(got) == 5 and got.state is None
    assert cli.fetch_prefix_state(fresh, BT, len(got))[0] == 4


def test_a_state_of_another_session_or_boundary_is_never_served(hub):
    addr, reg, cli = hub
    system = list(range(2 * BT))
    a = np.asarray(system + list(range(1000, 1000 + 3 * BT)), np.uint64)
    b = np.asarray(system + list(range(2000, 2000 + 3 * BT)), np.uint64)
    sa = publish(addr, reg, a, 0, 5, states=(4,))
    sb = publish(addr, reg, b, 0, 5, states=(4,))
    for tokens, want in ((a, sa[4]), (b, sb[4])):
        found = cli.match_prefix(tokens, BT)
        assert found.boundary == 4
        assert found.state[0].key == kv.prefix_chain(tokens, BT)[3]
        got = cli.fetch_prefix_blocks(found, state=found.state)
        assert got.state.tobytes() == want.tobytes()
    # A prompt that shares a's first three pages and not its fourth: the
    # run is three pages, and no state of a's lies at or under it.
    branch = a.copy()
    branch[3 * BT] += 1
    found = cli.match_prefix(branch, BT)
    assert len(found) == 3 and found.boundary == 0 and found.state == []
    # Another state under a boundary that holds one: refused, never
    # aliased.
    keys = kv.prefix_chain(a, BT)
    other, _ = kv.prefix_publish_state(keys[3], 4, page_bytes(99, STATE),
                                       a[12:16], node=addr)
    answer = reg.put_prefix_many([other], lease_ms=60000)[0]
    assert isinstance(answer, kv.KvStaleError)
    found = cli.match_prefix(a, BT)
    got = cli.fetch_prefix_blocks(found, state=found.state)
    assert got.state.tobytes() == sa[4].tobytes()


def test_states_demote_promote_and_drop_in_the_one_touch_order(hub):
    """Pages and states through a store whose hot tier holds two states
    and whose total holds three, against the reference's model block for
    block: a state is demoted, promoted on a hit and dropped as a page
    is, by its size."""
    addr, reg, cli = hub
    hot, total = 2 * STATE + 8 * PAGE, 3 * STATE + 10 * PAGE
    set_flag("trpc_kv_prefix_hot_bytes", str(hot))
    set_flag("trpc_kv_store_bytes", str(total))
    sizes = {"page": PAGE, "state": STATE}
    model = ref.StoreModel(total, hot, lambda blk: sizes[ref.kind(blk)])
    prompts = [np.arange(3 * BT, dtype=np.uint64) + 10000 * (s + 1)
               for s in range(4)]
    before = counted()
    for s, tokens in enumerate(prompts):
        publish(addr, reg, tokens, 0, 2, states=(2,))
        # The store admits the state first, then the run's pages.
        for block in [(ref.STATE, s, 2), (s, 0), (s, 1)]:
            model.publish(block)
        assert kv.prefix_hot_bytes() == model.bytes["hot"]
        assert kv.prefix_cold_bytes() == model.bytes["cold"]
    # Session 1's state is in the heap tier: its fetch promotes it.
    assert (ref.STATE, 1, 2) in model.cold
    found = cli.match_prefix(prompts[1], BT)
    # One fetch in flight: the state, then each page.
    assert cli.fetch_prefix_blocks([], state=found.state).state is not None
    for group in found:
        assert len(cli.fetch_prefix_blocks([group], window=1)) == 1
    for block in [(ref.STATE, 1, 2), (1, 0), (1, 1)]:
        assert model.fetch(block)
    assert kv.prefix_hot_bytes() == model.bytes["hot"]
    assert kv.prefix_cold_bytes() == model.bytes["cold"]
    got = delta(before)
    for counter, count in (("demote", "demote"), ("dropped", "dropped"),
                           ("promote", "promote"), ("cold_hits", "cold_hits"),
                           ("publish_total", "published")):
        assert got.get("kv_prefix_" + counter, 0) == model.counts[count], (
            counter)
    # The state and both its pages came back from the heap tier.
    assert model.counts["dropped_state"] > 0 and model.counts["promote"] == 3
    # The one dropped first was session 0's state, the least recently
    # touched: its prompt falls back to nothing.
    assert (ref.STATE, 0, 2) not in model
    assert cli.fetch_prefix_state(prompts[0], BT, 2) == (0, None)


def test_the_counters_rise_as_documented(hub):
    addr, reg, cli = hub
    tokens = np.arange(6 * BT, dtype=np.uint64) + 40
    before = counted()
    publish(addr, reg, tokens, 0, 6, states=(3, 6))
    got = delta(before)
    assert got["kv_prefix_state_publish_total"] == 2
    assert got["kv_prefix_state_publish_bytes"] == 2 * STATE
    # Both kinds in the totals, one PutPrefixMany a run with its state.
    assert got["kv_prefix_publish_total"] == 8
    assert got["kv_prefix_publish_bytes"] == 6 * PAGE + 2 * STATE
    assert (got["kv_prefix_put_many_total"],
            got["kv_prefix_put_many_records"]) == (2, 8)
    before = counted()
    found = cli.match_prefix(tokens, BT)
    cli.fetch_prefix_blocks(found, state=found.state)
    got = delta(before)
    assert (got["kv_prefix_state_fetch_total"],
            got["kv_prefix_state_fetch_bytes"]) == (1, STATE)
    assert got["kv_prefix_fetch_total"] == 7 == got["kv_prefix_hot_hits"]
    assert got["kv_prefix_match_states"] == 1
    assert got.get("kv_prefix_match_states_shallow", 0) == 0


LANDING_COUNTERS = ("batch_resp_bytes", "batch_land_copy_bytes",
                    "rma_direct_tx_bytes", "rma_direct_fallback")


@pytest.mark.parametrize("use_shm", [True, False], ids=["shm", "tcp"])
def test_a_window_of_pages_and_the_state_land_in_one_registered_buffer(
        hub, use_shm):
    """One window of the cell: 64 pages of Kimi-Linear's 1,032,192 B and
    the state, fetched into ranges of ONE `RmaBuffer`.  Over the shm ring
    every record is put straight into its range, each call holding its
    own completion descriptor of the region, so nothing is copied out;
    over tcp there is no one-sided plane and every page lands by copy.
    Either way byte-identical."""
    addr, reg, shm_cli = hub
    page, n = 1032192, 64
    set_flag("trpc_kv_prefix_hot_bytes", str(256 << 20))
    set_flag("trpc_kv_store_bytes", str(512 << 20))
    tokens = np.arange(n * BT, dtype=np.uint64) + 9000
    keys = kv.prefix_chain(tokens, BT)
    pages = page_bytes(41, n * page).reshape(n, page)
    state = page_bytes(42, STATE)
    published, _ = kv.prefix_publish_state(
        keys[n - 1], n, state, tokens[(n - 1) * BT:], lease_ms=60000,
        node=addr)
    kv.publish_prefix_run(keys, 0, pages.reshape(-1),
                          [tokens[i * BT:(i + 1) * BT] for i in range(n)],
                          lease_ms=60000, node=addr, registry=reg,
                          state=published)
    cli = shm_cli if use_shm else kv.KvClient(addr, timeout_ms=20000,
                                              use_shm=False)
    try:
        found = cli.match_prefix(tokens, BT)
        assert len(found) == n and found.state
        with RmaBuffer(n * page + STATE) as land:
            area = np.frombuffer(land.view, np.uint8)
            rows = area[:n * page].reshape(n, page)
            before = observe.Vars.dump()
            got = cli.fetch_prefix_blocks(found, landing=rows, window=n,
                                          state=found.state,
                                          state_landing=area[n * page:])
            after = observe.Vars.dump()
            moved = {k: after.get(k, 0) - before.get(k, 0)
                     for k in LANDING_COUNTERS}
            assert len(got) == n and got.state is not None
            assert np.array_equal(rows, pages)
            assert np.array_equal(area[n * page:], state)
            del got, rows, area
    finally:
        if not use_shm:
            cli.close()
    restored = n * page + STATE
    assert moved["batch_resp_bytes"] == restored
    assert moved["rma_direct_fallback"] == 0
    if use_shm:
        assert moved["batch_land_copy_bytes"] == 0
        assert moved["rma_direct_tx_bytes"] == restored
    else:
        # Every page is copied out of its frame; the state, over the
        # stripe threshold, is striped into its range chunk by chunk.
        assert moved["batch_land_copy_bytes"] == n * page
        assert moved["rma_direct_tx_bytes"] == 0


def test_page_keys_stay_bit_identical():
    """The fleet's dedup key is the parent commit's (test_kv_prefix_lanes'
    golden value), whether the tokens come as a list or as an array, and
    a state's content hash is `content_hash`'s of its bytes and span."""
    page, toks = golden_page(), golden_tokens()
    assert kv.content_hash(page, np.asarray(toks, np.uint64)) == (
        GOLDEN["tokens"])
    assert kv.content_hash(page, np.asarray([], np.uint64)) == (
        GOLDEN["no_tokens"])
    tokens = list(range(3, 3 + 9 * BT))
    assert kv.prefix_chain(np.asarray(tokens, np.uint64), BT) == (
        kv.prefix_chain(tokens, BT))


def test_a_state_hash_is_its_content_hash(hub):
    addr, _reg, _cli = hub
    tokens = np.arange(2 * BT, dtype=np.uint64)
    keys = kv.prefix_chain(tokens, BT)
    data = page_bytes(77, STATE)
    meta, fresh = kv.prefix_publish_state(keys[1], 2, data, tokens[BT:],
                                          node=addr)
    assert fresh and meta.hash == kv.content_hash(data, tokens[BT:])
    assert (meta.flags, meta.depth, meta.length) == (
        kv.PREFIX_STATE, 2, STATE)
    again, fresh = kv.prefix_publish_state(keys[1], 2, data, tokens[BT:],
                                           node=addr)
    assert not fresh and again.hash == meta.hash


@pytest.mark.parametrize("seed", [4000040011, 2**31 + 5])
def test_the_store_is_the_references_model_turn_by_turn(hub, seed):
    """The cell's replay at toy sizes, one fetch in flight (the state,
    then each page, as the model touches them): every turn restores what
    the model's store gives it, and the counters its counts, with the
    newest state of a session withdrawn every fifth turn, so that the hit
    falls back to an older state, from either tier, or to nothing."""
    addr, reg, cli = hub
    mix = {"sessions": 3, "system_pages": 2, "history_pages": 3,
           "turn_pages": 1, "pool_pages": 60, "page_bytes": PAGE,
           "state_bytes": 2 * PAGE}
    hot, total = 40 * PAGE, 56 * PAGE
    set_flag("trpc_kv_prefix_hot_bytes", str(hot))
    set_flag("trpc_kv_store_bytes", str(total))
    sizes = {"page": PAGE, "state": 2 * PAGE}
    model = ref.StoreModel(total, hot, lambda blk: sizes[ref.kind(blk)])

    def content(block) -> np.ndarray:
        if ref.kind(block) == "state":
            return page_bytes(pages_ref.page_const(seed, *block[1:]),
                              2 * PAGE)
        return page_bytes(pages_ref.page_const(seed, *block))

    def toks(turn):
        return np.asarray(
            [t for o, i in turn.page_ids()
             for t in pages_ref.page_token_ids(seed, o, i, BT, 50000)],
            np.uint64)

    def prefill(turn, tokens, first):
        keys = kv.prefix_chain(tokens, BT)
        end = turn.prompt_pages
        state, _ = kv.prefix_publish_state(
            keys[end - 1], end, content(turn.state_id(end)),
            tokens[(end - 1) * BT:], lease_ms=60000, node=addr)
        model.publish(turn.state_id(end))
        ids = turn.page_ids()[first:]
        kv.publish_prefix_run(
            keys[first:], first, np.concatenate([content(b) for b in ids]),
            [tokens[i * BT:(i + 1) * BT] for i in range(first, end)],
            lease_ms=60000, node=addr, registry=reg, state=state)
        for block in ids:
            model.publish(block)

    for s in range(mix["sessions"]):
        turn = ref.history(mix, s)
        prefill(turn, toks(turn), 0)
    before = counted()
    seen = set()
    for n, turn in enumerate(ref.kv_prefix_linear_reference(mix, 45)):
        tokens = toks(turn)
        newest = turn.state_id(turn.context_pages)
        if n % 5 == 4 and newest in model:
            found = cli.match_prefix(tokens, BT)
            assert found.boundary == turn.context_pages
            kv.prefix_withdraw(found.state[0].hash)
            model.withdraw(newest)
        want = model.restore(turn, 1)
        found = cli.match_prefix(tokens, BT)
        target = found.boundary
        state = None
        if found.state:
            state = cli.fetch_prefix_blocks([], state=found.state).state
        restored = 0
        while restored < target:
            more = cli.fetch_prefix_blocks(found[restored:restored + 1],
                                           window=1)
            restored += len(more)
            if not more or state is None:
                break
        if target and (state is None or restored < target):
            below = min(restored, target - 1) if state is None else restored
            target, state = cli.fetch_prefix_state(tokens, BT, below)
            restored = min(restored, target)
        boundary = target if state is not None else 0
        restored = min(restored, boundary)
        assert (restored, boundary) == want, (n, turn)
        if boundary:
            assert state.tobytes() == content(
                turn.state_id(boundary)).tobytes()
        seen.add(boundary == turn.context_pages)
        prefill(turn, tokens, restored)
        assert kv.prefix_hot_bytes() == model.bytes["hot"] <= hot
        assert kv.prefix_cold_bytes() == model.bytes["cold"]
    assert seen == {True, False}
    got = delta(before)
    for counter, count in (("hot_hits", "hot_hits"),
                           ("cold_hits", "cold_hits"),
                           ("promote", "promote"), ("demote", "demote"),
                           ("dropped", "dropped"),
                           ("fetch_stale", "stale")):
        assert got.get("kv_prefix_" + counter, 0) == model.counts[count], (
            counter)
    assert model.counts["dropped_state"] > 0 and model.counts["stale"] > 0
