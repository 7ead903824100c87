"""The plain reference of the `kv_prefix` deployment: multi-turn sessions
whose prompts share prefixes, restored from a content-addressed pool of
KV pages under two budgets.  Python integers, dicts and lists and a
little jax.numpy: no program of the system under test, no chain key, no
content hash, no store, no registry, no pipeline.

What it says, from the seed and the traffic's keys alone:

- the order of turns (`turns`): which slot's session speaks next, a new
  session when the last has had its turns, each session's document and
  its token ids;
- what every page holds (`page_const`, `next_pages`): page `i` of a
  prompt is the run's base page with an odd 32-bit constant added to
  every 32-bit word, the constant a function of (seed, whose page it is,
  `i`): the system prompt's pages are the same for every session, a
  session's own pages are its own.  So a page's checksum follows in
  integers (`page_sum`), two different pages differ in every word, and
  bytes left over from another block fail an exact compare;
- what the two HBM pools hold after any run of writes (`PoolSums`).

And, replayed over the publishes and fetches in the order the driver
issued them, a dict-and-list model of the store's documented policy
(`StoreModel`: a block is dropped only when the total budget is passed,
expired blocks first (none here: the lease outlives the run), then the
least recently touched of the heap tier, then of the hot tier; the hot
budget demotes and never drops; a touch of a heap block, a fetch's or
the publish of content the store holds, brings it hot).
From it, for each turn, the admissible restored depth (`depth_band`).
The same model driving itself gives the hit share the policy reaches on
this traffic, and with `chain_aware` what a policy that drops a chain
from its tail would reach: numbers to compare with, not limits.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import random

MASK = 0xFFFFFFFF
SYSTEM = -1          # the owner of the shared system prompt's pages
WARM_UP = -2         # the owner of the pages a driver's warm-up makes


@dataclasses.dataclass(frozen=True)
class Turn:
    """One turn: the session (numbered from 0 in the order they start),
    its slot, which of its turns this is (from 1), and how many pages its
    prompt has: the system prompt's, the document's, and the pages the
    earlier turns appended."""

    session: int
    slot: int
    number: int
    prompt_pages: int
    system_pages: int

    def owner(self, page: int) -> int:
        return SYSTEM if page < self.system_pages else self.session

    def page_ids(self) -> list[tuple[int, int]]:
        """(owner, index) of every page of the prompt, in order: what
        names a page's content, and so a block of the pool."""
        return [(self.owner(i), i) for i in range(self.prompt_pages)]


def turns(seed: int, mix: dict):
    """The endless order of turns.  The next turn goes to the slot drawn
    with weights 1/k (k = 1..`sessions_live`); a session that has had
    its `turns` is replaced in its slot by a new one.  Session n's
    document is `doc_pages[perm[n % len]]`, a seeded permutation of the
    table, cycled, so every window sees the same multiset; each turn
    after the first appends `turn_pages`."""
    rng = random.Random(seed)
    table = list(mix["doc_pages"])
    order = rng.sample(range(len(table)), len(table))
    live = int(mix["sessions_live"])
    weights = [1.0 / k for k in range(1, live + 1)]
    system, per_turn = int(mix["system_pages"]), int(mix["turn_pages"])
    slots: list = [None] * live          # [session, turns had]
    started = 0
    while True:
        slot = rng.choices(range(live), weights)[0]
        if slots[slot] is None or slots[slot][1] >= int(mix["turns"]):
            slots[slot] = [started, 0]
            started += 1
        slots[slot][1] += 1
        session, number = slots[slot]
        doc = table[order[session % len(table)]]
        yield Turn(session, slot, number,
                   system + doc + per_turn * (number - 1), system)


def page_token_ids(seed: int, owner: int, index: int, page_tokens: int,
                   vocab: int) -> list[int]:
    """The token ids of page `index` of `owner`: a function of (seed,
    owner, index), so a later turn's prompt begins with the earlier
    turn's, and every session's with the system prompt."""
    bits = random.Random(f"{seed}:{owner}:{index}:tokens").getrandbits(
        32 * page_tokens)
    raw = bits.to_bytes(4 * page_tokens, "little")
    return [int.from_bytes(raw[at:at + 4], "little") % vocab
            for at in range(0, len(raw), 4)]


def tokens(seed: int, turn: Turn, page_tokens: int, vocab: int) -> list[int]:
    """The prompt's token ids, `page_tokens` a page."""
    return [t for owner, index in turn.page_ids()
            for t in page_token_ids(seed, owner, index, page_tokens, vocab)]


def page_const(seed: int, owner: int, index: int) -> int:
    """The odd 32-bit number added to every word of the base page to
    make page `index` of `owner`."""
    digest = hashlib.blake2b(f"{seed}:{owner}:{index}".encode(),
                             digest_size=4).digest()
    return int.from_bytes(digest, "little") | 1


def next_pages(base, consts):
    """base (layers, tokens, width) uint16, consts (n,) uint32 -> the n
    pages (n, layers, tokens, width): `reference_kv.page_words`' pairing
    of tokens into 32-bit words, the constant added to every word."""
    import jax.numpy as jnp

    from benchmark.reference_kv import page_words

    words = page_words(base)[None] + consts.astype(jnp.uint32)[
        :, None, None, None]
    return jnp.concatenate(
        [(words >> 16).astype(base.dtype), words.astype(base.dtype)], axis=2)


def page_sum(base_sum: int, words_per_page: int, const: int) -> int:
    """The checksum (`reference_kv.page_checksum`) of the base page with
    `const` added to each of its words."""
    return (int(base_sum) + words_per_page * const) & MASK


class PoolSums:
    """The per-slot checksums of the two HBM pools, followed in integers
    from the initial pools': a slot holds the checksum of the page
    written there last."""

    def __init__(self, seed: int, base_sum: int, words_per_page: int,
                 producing, admitting):
        self.seed, self.base_sum = seed, int(base_sum)
        self.words_per_page = words_per_page
        self.sums = {"producing": [int(x) for x in producing],
                     "admitting": [int(x) for x in admitting]}

    def write(self, pool: str, slots, page_ids) -> None:
        for slot, (owner, index) in zip(slots, page_ids):
            self.sums[pool][slot] = page_sum(
                self.base_sum, self.words_per_page,
                page_const(self.seed, owner, index))


class StoreModel:
    """The documented policy over blocks of one size, named by
    (owner, index).  Two lists in touch order, least recent first: a
    touch (a publish, of new content or of content the store holds, or
    a fetch) puts a block at the back of `hot`, a demote moves the front
    of `hot` to the back of `cold`, and a drop takes the front of
    `cold`, then of `hot`.  So `hot` is the blocks touched last and the
    whole is one order by last touch."""

    def __init__(self, total_blocks: int, hot_blocks: int,
                 chain_aware: bool = False):
        self.total, self.hot_room = total_blocks, hot_blocks
        self.chain_aware = chain_aware
        self.hot: collections.OrderedDict = collections.OrderedDict()
        self.cold: collections.OrderedDict = collections.OrderedDict()
        self.ever: set = set()
        self.counts = collections.Counter()

    def __contains__(self, block) -> bool:
        return block in self.hot or block in self.cold

    def __len__(self) -> int:
        return len(self.hot) + len(self.cold)

    def depth(self, page_ids) -> int:
        """The leading pages of a prompt the store holds."""
        for i, block in enumerate(page_ids):
            if block not in self:
                return i
        return len(page_ids)

    def _fit_hot(self) -> None:
        while len(self.hot) >= self.hot_room and self.hot:
            block, _ = self.hot.popitem(last=False)
            self.cold[block] = True
            self.counts["demote"] += 1

    def _victim(self):
        front = next(iter(self.cold or self.hot))
        if not self.chain_aware:
            return front
        # The tail of the chain the least recently touched block is in:
        # the deepest page of its session, and only when no session has
        # a page left the deepest page of the system prompt.
        owner = front[0]
        if owner == SYSTEM:
            owner = next((b[0] for tier in (self.cold, self.hot)
                          for b in tier if b[0] != SYSTEM), SYSTEM)
        return max((b for tier in (self.cold, self.hot) for b in tier
                    if b[0] == owner), key=lambda b: b[1])

    def publish(self, block) -> bool:
        """True if the bytes were admitted, False if live content was
        renewed."""
        self.ever.add(block)
        if block in self:
            self.counts["renewed"] += 1
            if block in self.cold and self.hot_room > 0:
                self._to_hot(block)
                self.counts["renew_promote"] += 1
            else:
                (self.hot if block in self.hot else self.cold).move_to_end(
                    block)
            return False
        while len(self) >= self.total and len(self):
            victim = self._victim()
            del (self.cold if victim in self.cold else self.hot)[victim]
            self.counts["dropped"] += 1
        if self.hot_room > 0:
            self._fit_hot()
            self.hot[block] = True
        else:
            self.cold[block] = True
        self.counts["published"] += 1
        return True

    def _to_hot(self, block) -> None:
        del self.cold[block]
        self._fit_hot()
        self.hot[block] = True

    def fetch(self, block) -> bool:
        """True if the block was served (and touched; a heap block is
        promoted), False if the store no longer holds it."""
        if block in self.hot:
            self.hot.move_to_end(block)
            self.counts["hot_hits"] += 1
            return True
        if block not in self.cold:
            self.counts["stale"] += 1
            return False
        self.counts["cold_hits"] += 1
        if self.hot_room > 0:
            self._to_hot(block)
            self.counts["promote"] += 1
        else:
            self.cold.move_to_end(block)
        return True


def blocks_of(budget_bytes: int, block_bytes: int) -> int:
    return int(budget_bytes) // int(block_bytes)


def depth_band(store: StoreModel, page_ids) -> tuple[int, int]:
    """(at least, at most): the restored depth a turn may show.  At most
    the leading pages ever published for this prompt.  At least what the
    model holds, when it is run with the total budget cut by
    `fetch_window_pages` blocks: the order in which the fetches of one
    window are served, and so touched, is the server's; every touch
    leaves its block hot, so the two tiers are one order by last touch,
    a block's place in it is then off by fewer blocks than a window
    holds, and a store that much smaller drops no later than the real
    one."""
    at_most = 0
    for block in page_ids:
        if block not in store.ever:
            break
        at_most += 1
    return store.depth(page_ids), at_most


def self_driven_hit_share(seed: int, mix: dict, n_turns: int,
                          total_blocks: int, hot_blocks: int,
                          chain_aware: bool) -> float:
    """Blocks served over prompt pages asked, in percent, when the model
    drives itself over the first `n_turns` turns: each turn restores the
    leading pages the model holds and publishes the rest."""
    store = StoreModel(total_blocks, hot_blocks, chain_aware)
    asked = served = 0
    for _, turn in zip(range(n_turns), turns(seed, mix)):
        ids = turn.page_ids()
        depth = store.depth(ids)
        for block in ids[:depth]:
            store.fetch(block)
        for block in ids[depth:]:
            store.publish(block)
        asked += len(ids)
        served += depth
    return 100.0 * served / asked if asked else 0.0


def kv_prefix_reference(seed: int, mix: dict, n_turns: int) -> list[Turn]:
    """The first `n_turns` turns of the run: the configuration file's
    `reference`."""
    return [turn for _, turn in zip(range(n_turns), turns(seed, mix))]
