"""Driver `stream_echo` on the CPU at its rehearsal sizes: its control (a
stream that hands two echoes over swapped, leaves one out, or changes one
word must read `correct` false), the sound run's own account (the
running checksum against the reference's, the high-water mark against
the reference's bound, the counters against each other), the reference's
two forms against each other, the mix against the configuration, and the
readers on made-up evidence.  Nothing here is a measurement.

On the chip, from the root of a checkout, at the timed sizes under
`run.run_cell`:
`python3 tests/benchmark_tests/test_stream_echo.py <fault> <seed> <seconds>`
(exit 0 when the run read `correct` false), and
`python3 tests/benchmark_tests/test_stream_echo.py window2M <seed> <seconds>`:
the cell with both windows at the 2 MB default, under the 4 MB chunk,
and two chunks open (what two such windows hold for one thread); exit 0
when it read `correct` true."""

import json
import pathlib
import shutil
import sys
import tempfile
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(pathlib.Path(__file__).parent)]

from benchmark import reference_stream  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.spans import Spans  # noqa: E402
from test_rehearsal import _rehearse, copy_tree, tiny  # noqa: E402,F401

CELL = "stream_echo.chunk4M_o6"
READERS = ("strm_credit_wait_us", "strm_acks_per_chunk", "strm_copy_share",
           "strm_write_us", "strm_read_us", "strm_h2d_rate",
           "strm_produce_roofline")
FAULTS = ("two_swapped", "one_dropped", "one_word_changed")


def faulty_read_block(real, fault: str, at: int):
    """`Stream.read_block` over a stream that, at its `at`-th read, does
    not hand over what arrived: the next two echoes come swapped, or one
    is left out, or one word of one is changed.  Every later read is the
    stream's own.  (A function, so that the class binds it as a method.)"""
    state = {"reads": 0, "held": None}

    def read_block(stream, timeout_ms: int = -1):
        state["reads"] += 1
        if state["held"] is not None:
            held, state["held"] = state["held"], None
            return held
        block = real(stream, timeout_ms)
        if state["reads"] != at:
            return block
        if fault == "two_swapped":
            state["held"] = block
            return real(stream, 5000)
        if fault == "one_dropped":
            return real(stream, 5000)
        block[4097] ^= 0x10
        return block

    return read_block


def faulty_reads(fault: str, at: int):
    """(the patched class attribute's owner, its name, the fault)."""
    from brpc_tpu.rpc.stream import Stream

    return Stream, "read_block", faulty_read_block(
        Stream.read_block, fault, at)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_stream_that_does_not_deliver_in_order_once_and_exact_fails_the_run(
        tiny, monkeypatch, fault):  # noqa: F811
    """The cell's control: the guarantee broken is ordered, exactly once,
    byte-exact.  Only because no two chunks share a word does an echo in
    another's place fail the compare, and only because the running
    checksum folds in order does a swap change it."""
    at = 40 + int(tiny.cell(CELL).traffic["warm_chunks"])
    monkeypatch.setattr(*faulty_reads(fault, at))
    result, notes = _rehearse(tiny, CELL)
    driver = next(n for n in notes if n["note"] == "driver")
    assert result["attempted"] > 40
    assert result["correct"] is False
    assert 0 < result["failed"] <= result["attempted"] + 2
    assert result["compared"]["failed_calls"] == {
        "value": result["failed"], "limit": 0}
    assert result["compared"]["running_checksum_differs"]["value"] == 1
    assert driver["running_checksum"] != driver["running_checksum_expected"]
    if fault == "two_swapped":
        assert driver["chunks_mismatched_on_device"] == 2
        assert driver["chunks_missing_at_drain"] == 0
    elif fault == "one_dropped":
        # Every echo after it stands in its successor's place, and the
        # last one the client waits for never comes.
        assert driver["chunks_mismatched_on_device"] > 2
        assert driver["chunks_missing_at_drain"] == 1
    else:
        assert driver["chunks_mismatched_on_device"] == 1
    assert driver["unread_within_bound"] is True


def test_the_sound_run_accounts_for_every_chunk_and_both_bounds(
        tiny):  # noqa: F811
    result, notes = _rehearse(tiny, CELL, seed=2**31 + 33)
    driver = next(n for n in notes if n["note"] == "driver")
    counted = next(n for n in notes if n["note"] == "counters")
    assert result["correct"] is True and result["failed"] == 0
    assert driver["transport"] == driver["transport_expected"] == "shm_ring"
    assert driver["chunks_read_back"] == driver["chunks_produced"]
    assert driver["chunks_read_back"] >= result["attempted"]
    assert driver["running_checksum"] == driver["running_checksum_expected"]
    # More open than a window holds, less than both: the gate works, and
    # neither end ever held more than the reference's bound.
    chunk, window = driver["chunk_bytes"], driver["window_bytes"]
    assert window < driver["chunks_open"] * chunk < 2 * window
    assert driver["unread_bound_bytes"] == reference_stream.unread_bound(
        window, chunk)
    assert chunk <= driver["unread_high_water_client_bytes"] <= driver[
        "unread_bound_bytes"]
    assert driver["unread_within_bound"] is True
    assert driver["unread_high_water_bytes"] >= driver[
        "unread_high_water_client_bytes"]
    assert counted["stream_credit_wait_us"] > 0
    # Both ends are in the process: a chunk is written twice and given
    # back twice, an ACK goes out once half a window has gathered, a
    # write wraps the chunk and a read copies it once.
    assert counted["stream_bytes_written"] == chunk * counted[
        "stream_chunks_written"]
    assert counted["stream_chunks_consumed"] == pytest.approx(
        counted["stream_chunks_written"], abs=2 * driver["chunks_open"])
    assert counted["stream_acks_sent"] == pytest.approx(
        counted["stream_chunks_consumed"] * chunk / (window // 2), rel=0.02)
    assert counted["stream_capi_read_copy_bytes"] == pytest.approx(
        counted["stream_bytes_written"] / 2, abs=2 * driver[
            "chunks_open"] * chunk)
    assert "stream_capi_write_copy_bytes" not in counted   # zero: left out
    # Same seed, same stream; another seed, another.
    again, again_notes = _rehearse(tiny, CELL, seed=2**31 + 33)
    other, other_notes = _rehearse(tiny, CELL, seed=8)

    def checksum(these):
        return next(n for n in these if n["note"] == "driver")[
            "seed_checksum"]

    assert checksum(again_notes) == driver["seed_checksum"]
    assert checksum(other_notes) != driver["seed_checksum"]
    assert again["correct"] is True and other["correct"] is True


def test_the_reference_followed_in_checksums_is_the_reference_held_whole():
    rng = np.random.default_rng(17)
    first = rng.integers(0, 1 << 32, 4096, dtype=np.uint32)
    delivered, running, (at_server, at_client) = (
        reference_stream.stream_echo_reference(
            first, [4096] * 40, 4 * 16384, 6))
    assert running == reference_stream.running_checksum_after(
        reference_stream.chunk_checksum(first), 4096, 40)
    assert max(at_server, at_client) <= reference_stream.unread_bound(
        4 * 16384, 16384)
    assert at_server >= 16384 and at_client >= 4 * 16384
    # In order, each once, and no word shared with the chunk before.
    whole = first
    for chunk in delivered:
        assert bool(np.all(chunk != whole))
        whole = reference_stream.next_chunk(whole)
        assert np.array_equal(chunk, whole)
    # The order is in the running checksum: two swapped change it.
    swapped = list(delivered)
    swapped[7], swapped[8] = swapped[8], swapped[7]
    folded = 0
    for chunk in swapped:
        folded = reference_stream.fold(
            folded, reference_stream.chunk_checksum(chunk))
    assert folded != running
    # More open than the two windows hold: the reference's one client
    # parks in its write too, and says so instead of hanging.
    with pytest.raises(RuntimeError, match="parks in its write"):
        reference_stream.stream_echo_reference(
            first, [4096] * 40, 16384, 6)
    # A chunk wider than the window still goes, one at a time.
    _, _, marks = reference_stream.stream_echo_reference(
        first, [4096] * 10, 8192, 2)
    assert marks == (16384, 16384)


def test_the_timed_mix_is_the_sources_chunk_and_works_the_credit_gate():
    cell = Manifest(ROOT).cell(CELL)
    cfg, mix = cell.config, cell.traffic
    assert mix["chunk_bytes"] == cfg["chunk_bytes"] == 4 << 20
    assert cfg["reduced"] == [] and cfg["transport"] == "shm_ring"
    assert mix["window_bytes"] == 4 * mix["chunk_bytes"]
    open_bytes = mix["chunks_open"] * mix["chunk_bytes"]
    assert mix["window_bytes"] < open_bytes < 2 * mix["window_bytes"]
    # The reference's one client, at the timed geometry in words of a
    # byte's size, does not park against its own echoes.
    first = np.arange(64, dtype=np.uint32)
    delivered, _, marks = reference_stream.stream_echo_reference(
        first, [1] * 64, 16, mix["chunks_open"])
    assert len(delivered) == 64 and max(marks) <= 16 + 4 - 1
    for key in ("chunk_bytes", "window_bytes"):
        assert mix["rehearsal"][key] * 8 == mix[key]


def test_windows_under_the_chunk_deliver_one_chunk_at_a_time(
        tiny, tmp_path):  # noqa: F811
    """Upstream's default window is half the source's chunk: every chunk
    overruns it, goes all the same, and is acknowledged by itself."""
    root = tmp_path / "narrow"
    shutil.copytree(tiny.root, root)
    path = root / "benchmark" / "traffic" / "chunk4M_o6.json"
    mix = json.loads(path.read_text())
    mix.update(window_bytes=mix["chunk_bytes"] // 2, chunks_open=2)
    path.write_text(json.dumps(mix))
    result, notes = _rehearse(Manifest(root), CELL)
    driver = next(n for n in notes if n["note"] == "driver")
    counted = next(n for n in notes if n["note"] == "counters")
    assert result["correct"] is True and result["attempted"] > 10
    assert driver["window_bytes"] < driver["chunk_bytes"]
    assert driver["unread_high_water_client_bytes"] == driver["chunk_bytes"]
    assert counted["stream_acks_sent"] == pytest.approx(
        counted["stream_chunks_consumed"], abs=4)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_reads_nothing(name):
    manifest = Manifest(ROOT)
    reader = manifest.reader(name)
    assert reader.DRIVERS == ("stream_echo",)
    empty = types.SimpleNamespace(
        counters={}, spans=Spans(), trace=None, t_open=0.0, t_close=1.0,
        call_s=[], bytes_per_call=4 << 20, device_kind="TPU v5 lite",
        notes={})
    assert reader.read(empty) is None
    entry = next(m for m in manifest.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["unit"] == reader.UNIT
    assert entry["moves"] in {m["name"] for m in manifest.cell(
        CELL).end_to_end}


def test_the_readers_divide_what_the_window_counted():
    spans = Spans()
    for chunk in range(4):
        at = 0.01 * chunk
        spans.add("write", at, at + 0.0010)
        spans.add("read", at + 0.002, at + 0.0028)
        spans.add("h2d", at + 0.003, at + 0.005)
    ev = types.SimpleNamespace(
        spans=spans, trace=None, t_open=0.0, t_close=1.0, call_s=[0.03] * 4,
        bytes_per_call=4_000_000, device_kind="TPU v5 lite", notes={},
        counters={"stream_chunks_written": 8.0, "stream_bytes_written": 32e6,
                  "stream_chunks_consumed": 8.0, "stream_acks_sent": 4.0,
                  "stream_credit_wait_us": 2400.0,
                  "stream_capi_read_copy_bytes": 16e6,
                  "stream_capi_write_copy_bytes": 0.0})

    def read(name):
        return Manifest(ROOT).reader(name).read(ev)

    assert read("strm_credit_wait_us") == 300.0
    assert read("strm_acks_per_chunk") == 0.5
    assert read("strm_copy_share") == 50.0
    assert read("strm_write_us") == pytest.approx(1000.0)
    assert read("strm_read_us") == pytest.approx(800.0)
    assert read("strm_h2d_rate") == pytest.approx(2.0)
    assert read("strm_produce_roofline") is None
    ev.trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_bm_strm_produce(1)", 100, 40000],
                ["jit_bm_strm_produce(1)", 500000, 40000],
                ["jit_bm_strm_verify(2)", 600000, 90000],
                ["jit_bm_produce(3)", 700000, 10]]},
            {"name": "XLA Ops", "events": [["%fusion.1 = x", 100, 60000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bm:produce", 0, 1000], ["bm:verify", 600000, 200000]]}]}]}
    # The chunk in and the chunk out in 40 us; only the driver's own
    # produce program counts.
    assert Manifest(ROOT).reader("strm_produce_roofline").program_hbm_bytes(
        4 << 20) == 8 << 20
    assert read("strm_produce_roofline") == pytest.approx(
        100 * 8e6 / 40e-6 / 819e9)
    assert read("strm_produce_roofline") < 100


def control_on_the_chip(fault: str, seed: int, seconds: float) -> dict:
    """One run of the cell at the timed sizes with `fault` in the
    client's reads from 40 chunks into the window on; the result line,
    whose `correct` must be false."""
    from benchmark import run

    manifest = Manifest(ROOT)
    at = 40 + int(manifest.cell(CELL).traffic["warm_chunks"])
    owner, name, faulty = faulty_reads(fault, at)
    real = getattr(owner, name)
    setattr(owner, name, faulty)
    try:
        result, notes = run.run_cell(manifest, CELL, seed, seconds, False)
    finally:
        setattr(owner, name, real)
    _print_driver_notes(notes)
    return result


def default_windows_on_the_chip(seed: int, seconds: float) -> dict:
    """The cell in a copy of the benchmark's tree whose mix grants the 2
    MB default window each way, under the 4 MB chunk: every chunk
    overruns its window, so one is on its way each way at a time, and
    the one client keeps two open (a third write would park against its
    own unread echo).  The result line: delivered, `correct` true, and a
    lower goodput."""
    from benchmark import run

    root = pathlib.Path(tempfile.mkdtemp(prefix="bm_window2M_"))
    try:
        copy_tree(root)
        path = root / "benchmark" / "traffic" / "chunk4M_o6.json"
        mix = json.loads(path.read_text())
        mix.update(window_bytes=2 << 20, chunks_open=2)
        path.write_text(json.dumps(mix))
        result, notes = run.run_cell(Manifest(root), CELL, seed, seconds,
                                     False)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    _print_driver_notes(notes)
    return result


def _print_driver_notes(notes) -> None:
    for note in notes:
        if note["note"] in ("driver", "counters", "spans"):
            print(json.dumps(note), flush=True)


if __name__ == "__main__":
    what, seed, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    if what == "window2M":
        line = default_windows_on_the_chip(seed, seconds)
        ok = line["correct"] is True and line["failed"] == 0
    else:
        line = control_on_the_chip(what, seed, seconds)
        ok = line["correct"] is False and line["failed"] > 0
    print(json.dumps(line), flush=True)
    sys.exit(0 if ok else 1)
