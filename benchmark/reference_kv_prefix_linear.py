"""The plain reference of the `kv_prefix_linear` deployment: agent
sessions of a hybrid linear-attention model whose long contexts are
restored from a content-addressed pool of KV pages and recurrent-state
snapshots under two budgets.  Python integers, dicts and lists and a
little jax.numpy: no program of the system under test, no chain key, no
content hash, no store, no registry, no pipeline.

What it says, from the seed and the traffic's keys alone:

- the fixed replay (`turns`): `sessions` sessions served round robin,
  each holding the shared system prompt and a history of its own when
  the first turn comes, each turn appending `turn_pages` pages; the seed
  decides every byte and none of the counts;
- what every page holds (`reference_kv_prefix.page_const`): page `i` of
  a session is the run's base page with an odd 32-bit constant of (seed,
  owner, `i`) added to every 32-bit word, the system prompt's pages the
  same for every session;
- what the recurrent state after `b` pages holds (`state_const`): the
  run's base state with `C(b)` added to every 32-bit word, where `C(b)`
  sums, over the prompt's first `b` pages, each page's checksum made
  odd.  So the state at `b + 4` is the state restored at `b` plus the
  four new pages' term, a stale or another session's state differs from
  the right one in every word, and a restored state that was wrong makes
  every later state of its session wrong;
- what the four HBM pools hold after any run of writes (`PoolSums`);
- the store's documented policy over blocks of two sizes (`StoreModel`,
  `reference_kv_prefix.StoreModel`'s order by last touch with budgets in
  bytes), replayed over the publishes and fetches in the order the
  driver issued them, and from it each turn's restored depth and state
  boundary: exact, because the hot tier holds a whole round
  (`round_fits_hot` says whether a mix's sizes keep that rule).
"""

from __future__ import annotations

import collections
import dataclasses

from benchmark import reference_kv_prefix as base

MASK = base.MASK
SYSTEM = base.SYSTEM
STATE = "state"          # the kind of a state block: (STATE, owner, boundary)


@dataclasses.dataclass(frozen=True)
class Turn:
    """One turn: the session (from 0), the round it falls in (round 0 is
    the first turn after the history was built), and the prompt: the
    context restored so far and `new_pages` pages more."""

    session: int
    round: int
    context_pages: int
    new_pages: int
    system_pages: int

    @property
    def prompt_pages(self) -> int:
        return self.context_pages + self.new_pages

    def owner(self, page: int) -> int:
        return SYSTEM if page < self.system_pages else self.session

    def page_ids(self) -> list[tuple[int, int]]:
        return [(self.owner(i), i) for i in range(self.prompt_pages)]

    def state_id(self, boundary: int) -> tuple:
        """The block of this session's state after `boundary` pages."""
        return (STATE, self.session, boundary)


def history(mix: dict, session: int) -> Turn:
    """What set-up builds for a session: its whole context, prefilled
    from page 0, as a turn that restores nothing."""
    system = int(mix["system_pages"])
    return Turn(session, -1, 0, system + int(mix["history_pages"]), system)


def turns(mix: dict):
    """The endless replay: round r gives session 0, 1, ... its turn, each
    restoring the context the turns before left and appending
    `turn_pages` pages."""
    sessions, per_turn = int(mix["sessions"]), int(mix["turn_pages"])
    first = int(mix["system_pages"]) + int(mix["history_pages"])
    r = 0
    while True:
        for s in range(sessions):
            yield Turn(s, r, first + per_turn * r, per_turn,
                       int(mix["system_pages"]))
        r += 1


def rounds_fit(mix: dict) -> int:
    """The rounds a session's slots of the admitting pool hold: its
    prompt never passes `pool_pages / sessions` pages."""
    per_session = int(mix["pool_pages"]) // int(mix["sessions"])
    first = int(mix["system_pages"]) + int(mix["history_pages"])
    return (per_session - first) // int(mix["turn_pages"])


def page_sum(seed: int, base_sum: int, words_per_page: int, owner: int,
             index: int) -> int:
    return base.page_sum(base_sum, words_per_page,
                         base.page_const(seed, owner, index))


def state_const(seed: int, base_sum: int, words_per_page: int,
                page_ids) -> int:
    """C(b) for a prompt whose first b pages are `page_ids`."""
    return sum(page_sum(seed, base_sum, words_per_page, *p) | 1
               for p in page_ids) & MASK


def state_sum(base_state_sum: int, words_per_state: int, const: int) -> int:
    """The checksum of the base state with `const` on every word."""
    return (int(base_state_sum) + words_per_state * const) & MASK


def state_at(base_state, const):
    """The base state (state layers, rows, 128) uint16 with `const` on
    each of its 32-bit words (`reference_kv.page_words`' pairing)."""
    import jax.numpy as jnp

    return base.next_pages(base_state,
                           jnp.asarray([const], dtype=jnp.uint32))[0]


class PoolSums:
    """The per-slot checksums of the four HBM pools, followed in integers
    from the initial pools': a slot holds the checksum of what was
    written there last."""

    def __init__(self, seed: int, base_sum: int, words_per_page: int,
                 base_state_sum: int, words_per_state: int, initial: dict):
        self.seed, self.base_sum = seed, int(base_sum)
        self.words_per_page = words_per_page
        self.base_state_sum = int(base_state_sum)
        self.words_per_state = words_per_state
        self.sums = {name: [int(x) for x in sums]
                     for name, sums in initial.items()}

    def pages(self, pool: str, slots, page_ids) -> None:
        for slot, (owner, index) in zip(slots, page_ids):
            self.sums[pool][slot] = page_sum(
                self.seed, self.base_sum, self.words_per_page, owner, index)

    def state(self, pool: str, slot: int, const: int) -> None:
        self.sums[pool][slot] = state_sum(self.base_state_sum,
                                          self.words_per_state, const)


class StoreModel(base.StoreModel):
    """`reference_kv_prefix.StoreModel` over blocks of two sizes, budgets
    in bytes, as the store keeps them: a publish first drops the least
    recently touched blocks, the heap tier's first, until the total
    takes the block, then demotes the hot tier's least recently touched
    until the hot budget takes it; a touch of a heap block promotes it
    the same way.  Blocks are named as the driver names them: (owner,
    index) a page, (STATE, session, boundary) a state."""

    def __init__(self, total_bytes: int, hot_bytes: int, sizes):
        super().__init__(total_bytes, hot_bytes)
        self.sizes = sizes                  # block -> bytes
        self.bytes = collections.Counter()  # tier -> bytes

    def _size(self, block) -> int:
        return self.sizes(block)

    def _fits_hot(self, incoming: int) -> None:
        while self.hot and self.bytes["hot"] + incoming > self.hot_room:
            block, _ = self.hot.popitem(last=False)
            self.cold[block] = True
            self.bytes["hot"] -= self._size(block)
            self.bytes["cold"] += self._size(block)
            self.counts["demote"] += 1

    def _to_hot(self, block) -> None:
        size = self._size(block)
        del self.cold[block]
        self.bytes["cold"] -= size
        self._fits_hot(size)
        self.hot[block] = True
        self.bytes["hot"] += size

    def publish(self, block) -> bool:
        self.ever.add(block)
        size = self._size(block)
        if block in self:
            self.counts["renewed"] += 1
            if block in self.cold:
                self._to_hot(block)
                self.counts["renew_promote"] += 1
            else:
                self.hot.move_to_end(block)
            return False
        while self and self.bytes["hot"] + self.bytes["cold"] + size > (
                self.total):
            tier = self.cold or self.hot
            victim, _ = tier.popitem(last=False)
            self.bytes["cold" if tier is self.cold else "hot"] -= (
                self._size(victim))
            self.counts["dropped"] += 1
            self.counts["dropped_" + kind(victim)] += 1
        if size <= self.hot_room:
            self._fits_hot(size)
            self.hot[block] = True
            self.bytes["hot"] += size
        else:
            self.cold[block] = True
            self.bytes["cold"] += size
        self.counts["published"] += 1
        return True

    def fetch(self, block) -> bool:
        if block in self.hot:
            self.hot.move_to_end(block)
            self.counts["hot_hits"] += 1
            return True
        if block not in self.cold:
            self.counts["stale"] += 1
            return False
        self.counts["cold_hits"] += 1
        self._to_hot(block)
        self.counts["promote"] += 1
        return True

    def _deepest_state(self, turn: Turn, below: int) -> int:
        """The deepest boundary at or under `below` at which a state of
        the turn's prompt was ever published (the registry's records:
        the lease outlives the run)."""
        return next((b for b in range(below, 0, -1)
                     if turn.state_id(b) in self.ever), 0)

    def restore(self, turn: Turn, window: int) -> tuple[int, int]:
        """The turn's restore as the client makes it, touching what it
        touches: (pages restored, the boundary of the state restored
        with them).  The match: the run of pages ever published, cut at
        the deepest boundary of it with a state; the state fetched in
        the first window's round, the pages a window at a time, every
        page of a window fetched and the run ending at its first miss;
        a state or a page under it that did not come ends the hit at the
        deepest boundary under it whose state comes, or at 0."""
        ids = turn.page_ids()
        run = 0
        while run < len(ids) and ids[run] in self.ever:
            run += 1
        boundary = self._deepest_state(turn, run)
        if not boundary:
            return 0, 0
        state_ok = self.fetch(turn.state_id(boundary))
        restored = 0
        while restored < boundary:
            part = ids[restored:min(restored + window, boundary)]
            served = [self.fetch(block) for block in part]
            got = served.index(False) if False in served else len(served)
            restored += got
            if got < len(part) or not state_ok:
                break
        if state_ok and restored == boundary:
            return restored, boundary
        below = min(restored, boundary - 1) if not state_ok else restored
        while below > 0:
            boundary = self._deepest_state(turn, below)
            if not boundary:
                break
            if self.fetch(turn.state_id(boundary)):
                return boundary, boundary
            below = boundary - 1
        return 0, 0

    def withdraw(self, block) -> None:
        """The block dropped by hand (a withdraw), as a drop."""
        for tier, name in ((self.hot, "hot"), (self.cold, "cold")):
            if block in tier:
                del tier[block]
                self.bytes[name] -= self._size(block)
                self.counts["dropped"] += 1


def kind(block) -> str:
    return "state" if block[0] == STATE else "page"


def round_bytes(mix: dict, rounds: int) -> int:
    """What one round touches when the sessions have had `rounds` turns
    since their history: every page of every live context (the system
    prompt once) and each session's restored and newly published
    state."""
    sessions, per_turn = int(mix["sessions"]), int(mix["turn_pages"])
    own = int(mix["history_pages"]) + per_turn * (rounds + 1)
    pages = int(mix["system_pages"]) + sessions * own
    return (pages * int(mix["page_bytes"])
            + 2 * sessions * int(mix["state_bytes"]))


def round_fits_hot(mix: dict, hot_bytes: int) -> bool:
    """The rule the cell is sized by: one round's blocks and a turn's
    publish fit the hot tier at the longest the pool allows, so that no
    live block goes cold and a turn's depth is exact."""
    incoming = (int(mix["turn_pages"]) * int(mix["page_bytes"])
                + int(mix["state_bytes"]))
    return round_bytes(mix, rounds_fit(mix)) + incoming <= hot_bytes


def kv_prefix_linear_reference(mix: dict, n_turns: int) -> list[Turn]:
    """The first `n_turns` turns of the replay: the configuration file's
    `reference`."""
    return [turn for _, turn in zip(range(n_turns), turns(mix))]
