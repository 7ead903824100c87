"""The plain reference of `models/kv_pool.py` and of the KV plane that
moves its pages (brpc_tpu/rpc/kv.py, cpp/net/kvstore.cc): the same
operations on the same data give the same pools and the same answers.

Dicts for the store, the registry and the decode side's lookup cache,
numpy copies for the transfer, plain `jax.numpy` indexing for the pools:
no kernel, no pipeline, no donation, no lease (a test's leases outlast
it).  A record is `(block_id, layer)`; an answer is one of "ok", "hit",
"miss", "stale", "exists", per record and in order wherever the system
answers per record.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


class KvDisaggReference:
    def __init__(self, prefill, decode):
        self.prefill = jnp.asarray(prefill)
        self.decode = jnp.asarray(decode)
        self.layers = self.prefill.shape[1]
        self.store: dict = {}      # record -> (generation, bytes), live
        self.minted: dict = {}     # record -> last generation, for ever
        self.registry: dict = {}   # record -> generation
        self.accepted: dict = {}   # record -> highest the registry took
        self.cached: dict = {}     # decode side: record -> generation

    def records(self, block_id: int) -> list:
        return [(block_id, layer) for layer in range(self.layers)]

    # ---- the pools ----

    def produce(self, slot: int, page) -> None:
        """The prefill side computed `page` into its pool at `slot`."""
        self.prefill = self.prefill.at[slot].set(jnp.asarray(page))

    def write(self, slot: int, page) -> None:
        """The decode side takes a landed page into its pool."""
        self.decode = self.decode.at[slot].set(jnp.asarray(page))

    # ---- the prefill side: store and registry ----

    def publish(self, block_id: int, slot: int) -> str:
        """The page at `slot`, one record a layer, published and
        registered; a page with a live record is refused whole."""
        records = self.records(block_id)
        if any(r in self.store for r in records):
            return "exists"
        page = np.array(self.prefill[slot])
        for record in records:
            generation = self.minted.get(record, 0) + 1
            self.minted[record] = generation
            self.store[record] = (generation, page[record[1]].copy())
        self.register([(r, self.store[r][0]) for r in records])
        return "ok"

    def register(self, offers) -> list[str]:
        """`offers`: (record, generation) pairs."""
        out = []
        for record, generation in offers:
            held = self.registry.get(record)
            if generation == 0 or generation < self.accepted.get(record, 0):
                out.append("stale")   # never minted, or a zombie's offer
            elif held == generation:
                out.append("exists")
            else:
                self.registry[record] = generation
                self.accepted[record] = generation
                out.append("ok")
        return out

    def evict(self, records) -> list[str]:
        return ["hit" if self.registry.pop(r, None) is not None else "miss"
                for r in records]

    def withdraw(self, block_id: int) -> list[str]:
        return ["ok" if self.store.pop(r, None) is not None else "miss"
                for r in self.records(block_id)]

    # ---- the decode side ----

    def lookup(self, records) -> list[str]:
        out = []
        for record in records:
            if record not in self.cached and record in self.registry:
                self.cached[record] = self.registry[record]
            out.append("hit" if record in self.cached else "miss")
        return out

    def _serve(self, record, generation) -> str:
        """The store's answer to a fetch at `generation`."""
        if record in self.store:
            return "hit" if self.store[record][0] == generation else "stale"
        return "stale" if record in self.minted else "miss"

    def fetch(self, block_id: int):
        """(the page or None, the answer per record).  A cached lookup is
        used until the store proves it stale; then it is dropped, the
        registry asked again, and the record tried once more."""
        records = self.records(block_id)
        answers = []
        for record in records:
            answer = "miss"
            for attempt in range(2):
                if self.lookup([record]) == ["miss"]:
                    answer = "miss"
                    break
                answer = self._serve(record, self.cached[record])
                if answer == "hit" or attempt == 1:
                    break
                del self.cached[record]
            answers.append(answer)
        if answers != ["hit"] * len(records):
            return None, answers
        return np.stack([self.store[r][1] for r in records]), answers
