"""Driver `kv_prefix_linear` on the CPU at its rehearsal sizes: the sound
run against the reference (every turn's depth and state boundary the
model's, bytes, pool checksums, budgets, the counters' identities, the
window's demotes and drops), its control (the state of the previous
boundary served as the newest, another session's state served, one word
of one page changed: each must read `correct` false), the program that
lacks the state kind refused before set-up, the mix against the
configuration's widths and the hot tier's rule, and the reference's own
properties.  Nothing here is a measurement.

On the chip the control is `control_on_the_chip` below, run as
`python3 tests/benchmark_tests/test_kv_prefix_linear.py <fault> <seed>
<seconds>` from the root of a checkout: the same faults under
`run.run_cell` at the timed sizes.  `... record_trace <seed> <seconds>`
runs the cell traced and writes a cut of its device trace to
`chiprun_out/trace_kv_prefix_linear.json.gz`, the readers' recorded
trace (`benchmark/testdata/`)."""

import gzip
import json
import os
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(pathlib.Path(__file__).parent)]

from benchmark import reference_kv_prefix as pages_ref  # noqa: E402
from benchmark import reference_kv_prefix_linear as ref  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from test_rehearsal import _rehearse, tiny  # noqa: E402,F401  (fixture)

CELL = "kv_prefix_linear.agent4_128k_replay"
FAULTS = ("previous_state_served", "another_session_state_served",
          "one_word_changed")
CHECKS = ("turns_refused", "pages_or_state_differ", "turns_off_the_model",
          "states_of_another_boundary_or_session", "budget_passed",
          "pool_slots_differing_from_reference", "served_not_hot_plus_cold",
          "state_matches_over_matches")


def faulty_fetch(real, fault: str, after: int, sessions: int):
    """`KvClient.fetch_prefix_blocks` over a store or a transport that,
    from the `after`-th turn on (a turn's first fetch carries its state),
    delivers a wrong state or a wrong page: the state the same session
    restored a round before (the previous boundary's) or the state the
    turn before restored (another session's), in the place of the right
    one; or one 32-bit word of one landed page changed."""
    states: list = []      # the right states of the last `sessions` turns
    turns = 0

    def fetch(self, groups, landing=None, window=16, state=None,
              state_landing=None):
        nonlocal turns
        got = real(self, groups, landing=landing, window=window,
                   state=state, state_landing=state_landing)
        turns += bool(state)
        right = got.state.copy() if got.state is not None else None
        if turns > after:
            if fault == "one_word_changed" and got:
                page = got[len(got) // 2].view(np.uint32)
                page[len(page) // 2] ^= 1
            elif right is None:
                pass
            elif fault == "previous_state_served" and len(states) == sessions:
                got.state[:] = states[0]
            elif fault == "another_session_state_served" and states:
                got.state[:] = states[-1]
        if right is not None:
            states.append(right)
            del states[:-sessions]
        return got

    return fetch


def inject(patch, mix: dict, fault: str) -> None:
    """`patch(owner, name, value)` sets the faulty part in place, from
    two turns into the window on."""
    from brpc_tpu.rpc import kv

    sessions = int(mix["sessions"])
    patch(kv.KvClient, "fetch_prefix_blocks", faulty_fetch(
        kv.KvClient.fetch_prefix_blocks, fault,
        sessions * int(mix["warm_rounds"]) + 2, sessions))


@pytest.mark.parametrize("fault", FAULTS)
def test_a_restore_with_a_wrong_state_or_page_fails_the_run(
        tiny, monkeypatch, fault):  # noqa: F811
    """The cell's control: the guarantee broken is that a turn restores
    the state of its own prompt at its own boundary, byte-exact, with
    byte-exact pages.  Only because the state after each page differs in
    every word does a stale or another session's state fail the
    compare."""
    inject(monkeypatch.setattr, tiny.cell(CELL).traffic, fault)
    result, notes = _rehearse(tiny, CELL)
    driver = next(n for n in notes if n["note"] == "driver")
    assert result["attempted"] > 4
    assert result["correct"] is False and result["failed"] > 0
    assert result["compared"]["failed_calls"] == {
        "value": result["failed"], "limit": 0}
    assert driver["pages_or_state_differ"] > 0
    assert result["compared"]["pages_or_state_differ_differs"] == {
        "value": 1, "limit": 0}


def test_the_sound_run_restores_what_the_model_says_and_matches_it(
        tiny):  # noqa: F811
    result, notes = _rehearse(tiny, CELL, seed=2**31 + 41)
    driver = next(n for n in notes if n["note"] == "driver")
    counted = next(n for n in notes if n["note"] == "counters")
    spans = next(n for n in notes if n["note"] == "spans")
    assert result["correct"] is True and result["failed"] == 0
    assert driver["transport"] == driver["transport_expected"] == "shm_ring"
    for name in CHECKS:
        assert driver[name] == 0 == driver[name + "_expected"]
        assert result["compared"][name + "_differs"] == {
            "value": 0, "limit": 0}
    counts = driver["counts"]
    assert counts["fallbacks"] == 0
    assert counts["states_restored"] == counts["turns"]
    # Every turn restored its whole context and prefilled one turn's
    # pages: the histories were prefilled whole.
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "agent4_128k_replay.json").read_text())["rehearsal"]
    assert counts["pages_prefilled"] == (
        4 * (mix["system_pages"] + mix["history_pages"])
        + counts["turns"] * mix["turn_pages"])
    # One Match a turn, each with the state at the run's end; one state
    # fetched a turn, one-sided; the pages in the ring's frames.
    turns = counted["kv_prefix_match_total"]
    assert counted["kv_prefix_match_states"] == turns
    assert "kv_prefix_match_states_shallow" not in counted
    assert counted["kv_prefix_state_fetch_total"] == turns
    assert counted["kv_prefix_state_fetch_bytes"] == turns * mix[
        "state_bytes"]
    assert counted["rma_tx_bytes"] == counted["kv_prefix_state_fetch_bytes"]
    assert counted["kv_prefix_state_publish_total"] == turns
    assert counted["kv_prefix_put_many_total"] == turns
    assert counted["kv_prefix_put_many_records"] == (
        turns * (mix["turn_pages"] + 1))
    # The window demoted and dropped superseded states, none of its live
    # blocks went cold, and the model says the same counts.
    model = driver["reference_model_in_window"]
    for name in ("demote", "dropped"):
        assert counted["kv_prefix_" + name] == model[name] > 0, name
    assert model["dropped_state"] == model["dropped"]
    assert "kv_prefix_cold_hits" not in counted
    for name in ("match", "fetch", "state_fetch", "h2d", "state_h2d",
                 "write", "verify", "produce", "state_produce", "d2h",
                 "d2h_wait", "publish", "state_publish"):
        assert spans[name]["n"] > 0, name
    # Same seed, same bytes; another seed, others; the same counts.
    again, again_notes = _rehearse(tiny, CELL, seed=2**31 + 41)
    other, other_notes = _rehearse(tiny, CELL, seed=9)

    def seed_sum(these):
        return next(n for n in these if n["note"] == "driver")[
            "seed_checksum"]

    assert seed_sum(again_notes) == driver["seed_checksum"]
    assert seed_sum(other_notes) != driver["seed_checksum"]
    assert again["correct"] is True and other["correct"] is True


def test_a_program_without_the_state_kind_is_refused_before_set_up(
        tiny, monkeypatch):  # noqa: F811
    """What the parent commit is under this PR's benchmark files: the
    cell fails at once, with another exit code than 0, and says why."""
    from brpc_tpu.rpc import kv

    monkeypatch.delattr(kv, "prefix_publish_state")
    monkeypatch.delattr(kv.KvClient, "fetch_prefix_state")
    with pytest.raises(SystemExit, match="prefix_publish_state, "
                       "fetch_prefix_state of brpc_tpu.rpc.kv"):
        _rehearse(tiny, CELL)


def test_the_timed_mix_is_the_configurations_widths_and_holds_a_round():
    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    cfg, mix = cell.config, cell.traffic
    driver = manifest.driver("kv_prefix_linear")
    g = driver.geometry(cfg, mix)
    assert g["page_shape"] == (7, 128, 576)
    assert g["page_bytes"] == 1032192 == 7 * cfg["page_record_bytes"]
    assert g["state_shape"] == (20, 8480, 128)
    assert g["state_bytes"] == 43417600 == 20 * cfg["snapshot_record_bytes"]
    assert mix["pool_pages"] * g["page_bytes"] == cfg["page_pool_bytes"]
    assert mix["state_slots"] * g["state_bytes"] == cfg["state_pool_bytes"]
    assert 2 * (cfg["page_pool_bytes"] + cfg["state_pool_bytes"]) == (
        cfg["device_bytes"]) == 10902044672
    assert (g["hot_bytes"], g["store_bytes"]) == (5 << 30, 6 << 30)
    assert g["rounds_fit"] == 38 and g["per_session"] == 1152
    # 128,000 tokens a session when the window opens, 4 pages a turn.
    assert (mix["system_pages"] + mix["history_pages"]) * 128 == 128000
    # The hot tier holds a round at the longest the pool allows: 4,432
    # pages and 8 states, and a turn's publish, under 5 GiB.
    assert ref.round_bytes(mix, 38) == 4432 * 1032192 + 8 * 43417600
    assert ref.round_fits_hot(mix, 5 << 30)
    assert not ref.round_fits_hot(mix, 4 << 30)
    assert cfg["reduced"] == [] and cell.chips == 1
    # Every number of the catalog's row for the model is in the file,
    # where `MODEL_CATALOG` names a JSON-lines catalog of architectures.
    catalog = pathlib.Path(os.environ.get("MODEL_CATALOG", ""))
    if catalog.is_file():
        row = next(json.loads(line) for line in catalog.read_text().split(
            "\n") if '"Kimi-Linear-48B-A3B-Instruct"' in line)
        assert cfg["model_config"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"]} == row["config"]
    with pytest.raises(ValueError, match="disagree"):
        driver.geometry(cfg, dict(mix, page_bytes=147456))
    with pytest.raises(ValueError, match="does not hold a round"):
        driver.geometry(cfg, dict(mix, pool_pages=8192))
    with pytest.raises(ValueError, match="disagree"):
        driver.geometry(cfg, dict(mix, turn_pages=3))


def test_the_reference_replays_the_same_turns_and_follows_the_state():
    import jax.numpy as jnp

    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "agent4_128k_replay.json").read_text())
    turns = ref.kv_prefix_linear_reference(mix, 12)
    # Round robin, 1,000 pages restored in the first round, 4 more a
    # round; no seed in it: every run is this replay.
    assert [t.session for t in turns] == [0, 1, 2, 3] * 3
    assert [t.context_pages for t in turns] == [1000] * 4 + [1004] * 4 + [
        1008] * 4
    assert all(t.prompt_pages == t.context_pages + 4 for t in turns)
    ids = turns[5].page_ids()
    assert ids[:64] == [(ref.SYSTEM, i) for i in range(64)]
    assert ids[64:] == [(1, i) for i in range(64, 1008)]
    assert ref.history(mix, 2).prompt_pages == 1000
    # C(b): the state after b pages is the base state with the pages'
    # odd checksums summed on every word; the state at b + 4 is the one
    # at b and the four pages' term; another session's differs.
    base_sum, wpp = 12345, 7 * 64 * 576
    page_ids = turns[0].page_ids()
    c = [ref.state_const(5, base_sum, wpp, page_ids[:b]) for b in (1000,
                                                                   1004)]
    four = ref.state_const(5, base_sum, wpp, page_ids[1000:1004])
    assert c[1] == (c[0] + four) & ref.MASK and c[0] != c[1]
    assert c[0] != ref.state_const(5, base_sum, wpp,
                                   turns[1].page_ids()[:1000])
    assert c[0] == ref.state_const(5, base_sum, wpp, page_ids[:1000])
    assert c[0] != ref.state_const(6, base_sum, wpp, page_ids[:1000])
    rng = np.random.default_rng(41)
    state = jnp.asarray(rng.integers(0, 1 << 16, (2, 8, 4), dtype=np.uint16))
    from benchmark import reference_kv

    state_sum = int(reference_kv.page_checksum(state))
    for const in (0, 1, c[0]):
        got = ref.state_at(state, const)
        assert got.shape == state.shape and got.dtype == state.dtype
        assert int(reference_kv.page_checksum(got)) == ref.state_sum(
            state_sum, 2 * 8 * 4 // 2, const)
    assert bool(jnp.all(reference_kv.page_words(ref.state_at(state, c[0]))
                        != reference_kv.page_words(ref.state_at(state,
                                                                c[1]))))
    assert pages_ref.page_const(5, ref.SYSTEM, 3) & 1


def test_the_store_model_keeps_a_round_hot_and_drops_superseded_states():
    """The model over the replay at the timed sizes, for the longest the
    pool allows: no live block ever goes cold, every turn's depth and
    boundary is its whole context, and what is demoted and dropped is
    superseded states only."""
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "agent4_128k_replay.json").read_text())
    sizes = {"page": mix["page_bytes"], "state": mix["state_bytes"]}
    model = ref.StoreModel(6 << 30, 5 << 30,
                           lambda block: sizes[ref.kind(block)])
    last = mix["turn_pages"]     # the last piece a prefill publishes

    def prefill(turn, first):
        # As the driver: the pieces, the state, then the last piece.
        ids = turn.page_ids()
        for block in ids[first:-last]:
            model.publish(block)
        model.publish(turn.state_id(turn.prompt_pages))
        for block in ids[-last:]:
            model.publish(block)

    for s in range(mix["sessions"]):
        prefill(ref.history(mix, s), 0)
    for turn in ref.kv_prefix_linear_reference(
            mix, mix["sessions"] * ref.rounds_fit(mix)):
        depth, boundary = model.restore(turn, mix["fetch_window_pages"])
        assert depth == boundary == turn.context_pages, turn
        prefill(turn, depth)
        assert model.bytes["hot"] <= 5 << 30
        assert model.bytes["hot"] + model.bytes["cold"] <= 6 << 30
    counts = model.counts
    assert counts["cold_hits"] == counts["stale"] == 0
    assert counts["demote"] > 0 and counts["dropped"] > 0
    assert counts["dropped_state"] == counts["dropped"]
    assert all(ref.kind(b) == "state" for b in model.cold)


def _trace_cut(trace: dict, seconds: float = 1.5) -> dict:
    """The first `seconds` of a traced window: every line's events that
    start in it."""
    from benchmark import trace_reduce

    lo, _ = trace_reduce.window_ns(trace)
    hi = lo + seconds * 1e9
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"],
             "events": [e for e in ln["events"]
                        if lo <= e[1] and e[1] + e[2] <= hi]}
            for ln in p["lines"]]}
        for p in trace["planes"]]}


def record_trace(seed: int, seconds: float) -> dict:
    """One traced run of the cell at the timed sizes; a cut of its trace
    written under `chiprun_out/`."""
    from benchmark import run, trace_reduce

    kept = {}

    def load(trace_dir):
        kept["trace"] = trace_reduce.load_xplane(trace_dir)
        return kept["trace"]

    result, notes = run.run_cell(Manifest(ROOT), CELL, seed, seconds, True,
                                 load_trace=load)
    out = ROOT / "chiprun_out" / "trace_kv_prefix_linear.json.gz"
    out.parent.mkdir(exist_ok=True)
    with gzip.open(out, "wt") as f:
        json.dump(_trace_cut(kept["trace"]), f)
    for note in notes:
        print(json.dumps(note), flush=True)
    return result


def control_on_the_chip(fault: str, seed: int, seconds: float) -> dict:
    """One run of the cell at the timed sizes with `fault` from two turns
    into the window on; the result line, whose `correct` must be
    false."""
    from benchmark import run

    manifest = Manifest(ROOT)
    undo = []

    def patch(owner, name, value):
        undo.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    inject(patch, manifest.cell(CELL).traffic, fault)
    try:
        result, notes = run.run_cell(manifest, CELL, seed, seconds, False)
    finally:
        for owner, name, value in undo:
            setattr(owner, name, value)
    for note in notes:
        if note["note"] == "driver":
            print(json.dumps(note), flush=True)
    return result


if __name__ == "__main__":
    if sys.argv[1] == "record_trace":
        line = record_trace(int(sys.argv[2]), float(sys.argv[3]))
        print(json.dumps(line), flush=True)
        sys.exit(0 if line["correct"] else 1)
    line = control_on_the_chip(sys.argv[1], int(sys.argv[2]),
                               float(sys.argv[3]))
    print(json.dumps(line), flush=True)
    sys.exit(0 if line["correct"] is False and line["failed"] > 0 else 1)
