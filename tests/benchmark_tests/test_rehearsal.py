"""Each driver end to end on the CPU at tiny sizes, through run.py's own
functions: the traffic files of a copy of the tree are shrunk to what
each says under `rehearsal` (sizes are data, and so are a rehearsal's),
Pallas runs interpreted, the mesh is four of the virtual CPU devices, and
the device trace is the one recorded on the chip under the driver's name.
Nothing here names a cell, a mix or a driver of its own accord, so a cell
a later PR adds as files is rehearsed as it stands.  Nothing here is a
measurement."""

import gzip
import json
import pathlib
import shutil
import subprocess
import sys

import pytest

from benchmark import peaks, run
from benchmark.manifest import Manifest
from benchmark.payload import ReusedArray, SendOnce

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}


def copy_tree(root) -> None:
    """BENCHMARK.json and the benchmark's own directory, copied to `root`."""
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))


def shrink_traffic(root) -> None:
    """Every mix under `root` overwritten with its own `rehearsal` sizes.
    A `seconds` there, which no driver reads, is the rehearsal's window
    for that mix (`_rehearse`), where the one second that is the rule
    leaves a tail too few samples to be read."""
    for path in sorted((root / "benchmark" / "traffic").glob("*.json")):
        mix = json.loads(path.read_text())
        if not isinstance(mix.get("rehearsal"), dict):
            raise KeyError(
                f"{path.name} has no `rehearsal` object: the keys the CPU "
                "rehearsal overwrites, {} where the mix is small already")
        mix.update(mix["rehearsal"], trace_seconds=0.5)
        path.write_text(json.dumps(mix))


def recorded_trace(manifest, driver_name: str) -> pathlib.Path:
    """The trace recorded on the chip for this driver, by its name."""
    path = manifest.home / "testdata" / f"trace_{driver_name}.json.gz"
    if not path.is_file():
        raise FileNotFoundError(
            f"driver {driver_name!r} has no recorded trace {path}: the "
            "CPU has no device plane, so a traced rehearsal reads one")
    return path


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The benchmark's tree with its traffic shrunk to a rehearsal."""
    root = tmp_path_factory.mktemp("tiny")
    copy_tree(root)
    shrink_traffic(root)
    return Manifest(root)


def _rehearse(manifest, cell, seed=7, trace=False):
    def recorded(_trace_dir):
        path = recorded_trace(manifest, manifest.cell(cell).driver_name)
        with gzip.open(path, "rt") as f:
            return json.load(f)

    seconds = float(manifest.cell(cell).traffic.get("seconds", 1.0))
    return run.run_cell(manifest, cell, seed, seconds, trace,
                        platform="cpu", interpret=True, load_trace=recorded)


@pytest.fixture(scope="module")
def untraced(tiny):
    return {cell: _rehearse(tiny, cell) for cell in tiny.cell_names()}


@pytest.mark.parametrize("cell", Manifest(ROOT).cell_names())
def test_untraced_run_prints_the_cells_end_to_end_metrics(
        tiny, untraced, cell):
    result, notes = untraced[cell]
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {
        m["name"] for m in tiny.cell(cell).end_to_end}
    for m in tiny.cell(cell).end_to_end:
        got = result["metrics"][m["name"]]
        assert set(got) == {"value", "unit"} and got["unit"] == m["unit"]
        assert got["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"
    # Each number compared beside its limit, as the line's last key.
    assert list(result)[-1] == "compared"
    assert "failed_calls" in result["compared"]
    for c in result["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(result)
    driver_note = next(n for n in notes if n["note"] == "driver")
    if tiny.cell(cell).driver_name == "served_echo":
        assert driver_note["transport"] == tiny.cell(cell).config["transport"]
        assert driver_note["requests_sent_once"] >= result["attempted"]


@pytest.mark.parametrize("cell", Manifest(ROOT).cell_names())
def test_traced_run_prints_every_per_layer_metric_and_a_breakdown(
        tiny, cell, tmp_path, monkeypatch):
    # The recorded traces are a v5e's; the table of peaks has no row for
    # the CPU and must not get one, so the rehearsal reads a copy that
    # calls the CPU by the v5e's numbers.
    table = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    table["cpu"] = table["TPU v5 lite"]
    (tmp_path / "peaks.json").write_text(json.dumps(table))
    monkeypatch.setattr(peaks, "_TABLE", tmp_path / "peaks.json")
    result, _ = _rehearse(tiny, cell, trace=True)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True
    assert set(result["metrics"]) == {
        m["name"] for m in tiny.cell(cell).per_layer}
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    for rows in result["breakdown"].values():
        assert 1 <= len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)


class _FaultyPipeline:
    """A pipeline over a transport that, from its `after`-th completion
    on (when every response buffer has been used before), does not
    deliver what `fault` names: the bytes stay what they were before the
    call, or are those of the call that completed before."""

    def __init__(self, real, fault: str, after: int):
        self._real, self._fault, self._after = real, fault, after
        self._was: dict = {}
        self._completed = 0
        self._previous = None

    def submit(self, method, requests, resp_bufs=None, **kw):
        before = [buf.copy() for buf in resp_bufs]
        tokens = self._real.submit(method, requests, resp_bufs=resp_bufs,
                                   **kw)
        self._was.update(zip(tokens, zip(resp_bufs, before)))
        return tokens

    def poll(self, **kw):
        done = self._real.poll(**kw)
        for c in done:
            buf, before = self._was.pop(c.token)
            self._completed += 1
            tail = slice(len(buf) - len(buf) // 4, None)
            if self._completed > self._after:
                if self._fault == "never_written":
                    buf[:] = before
                elif self._fault == "tail_never_written":
                    buf[tail] = before[tail]
                elif self._fault == "tail_of_another_call":
                    buf[tail] = self._previous[tail]
            self._previous = buf.copy()
        return done

    def close(self):
        self._real.close()


def _cells_of(driver_name: str) -> list[str]:
    manifest = Manifest(ROOT)
    return [name for name in manifest.cell_names()
            if manifest.cell(name).driver_name == driver_name]


@pytest.mark.parametrize("cell", _cells_of("served_echo"))
@pytest.mark.parametrize("fault", ["never_written", "tail_never_written",
                                   "tail_of_another_call"])
def test_a_response_the_transport_did_not_deliver_fails_the_run(
        tiny, monkeypatch, fault, cell):
    """The control of every served cell: the guarantee broken is that a
    response is byte-exact against its request.  The echo is a copy:
    only because every word of a request differs from the same word of
    every other does a stale or misdelivered chunk fail the compare."""
    from brpc_tpu.rpc import Channel

    real = Channel.pipeline
    # 40 calls into the window: a mismatch of the warm-up would count in
    # `failed` and in no `attempted`.
    after = 40 + int(tiny.cell(cell).traffic["warm_calls"])
    monkeypatch.setattr(
        Channel, "pipeline",
        lambda self: _FaultyPipeline(real(self), fault, after=after))
    result, notes = _rehearse(tiny, cell)
    driver_note = next(n for n in notes if n["note"] == "driver")
    fused_from = tiny.driver("served_echo").FUSED_FROM_BYTES
    assert (driver_note["device_step"] == "echo_fused") == (
        tiny.cell(cell).traffic["payload_bytes"] >= fused_from)
    assert result["attempted"] > 40
    assert result["correct"] is False
    assert 0 < result["failed"] <= result["attempted"]
    compared = result["compared"]["failed_calls"]
    assert compared == {"value": result["failed"], "limit": 0}


def test_an_exchange_left_out_between_the_chips_fails_the_run(
        tiny, monkeypatch):
    """The mesh cell's control: every peer keeps the rows it had, and its
    checksums are right for what it holds; only the compare against the
    reference's transposition sees it."""
    from brpc_tpu.transport.ici import IciTransport

    monkeypatch.setattr(IciTransport, "all_to_all",
                        lambda self, local, *a, **kw: local)
    result, _ = _rehearse(tiny, "mesh_nton.exchange64M")
    assert result["attempted"] > 0
    assert result["correct"] is False and result["failed"] > 0
    assert result["compared"]["failed_calls"]["value"] > 0


def test_a_mix_with_one_call_in_flight_is_data_only(tiny, tmp_path):
    """PERF.md's next row: the `sync64M` mix on `echo_shm`, the wire in
    series with the staging."""
    root = tmp_path / "sync1"
    shutil.copytree(tiny.root, root)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"].append({
        "name": "echo_shm.sync64M", "config": "echo_shm",
        "traffic": "sync64M", "chips": 1, "why": "a later PR's cell"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "echo_shm.tensor64M" in m.get("workloads", []):
            m["workloads"].append("echo_shm.sync64M")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    grown = Manifest(root)
    assert grown.cell("echo_shm.sync64M").traffic["calls_in_flight"] == 1
    result, _ = _rehearse(grown, "echo_shm.sync64M")
    assert result["correct"] is True and result["attempted"] > 0
    assert set(result["metrics"]) == {"goodput", "call_p50", "setup_s"}


def test_same_seed_same_payload_other_seed_another(tiny, untraced):
    def checksum(pair):
        return next(n for n in pair[1] if n["note"] == "driver")[
            "seed_checksum"]

    for cell in ("echo_tcp.small1K", "mesh_nton.exchange64M"):
        again = _rehearse(tiny, cell, seed=7)
        other = _rehearse(tiny, cell, seed=8)
        assert checksum(again) == checksum(untraced[cell])
        assert checksum(other) != checksum(untraced[cell])


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    cell = Manifest(ROOT).cell_names()[0]
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert done.returncode != 0
    assert done.stdout == ""
    assert "needs 1 tpu chip" in done.stderr


def test_an_unknown_device_kind_has_no_peak():
    assert peaks.peak("TPU v5 lite", "hbm_gbps") == 819.0
    assert peaks.peak("TPU v5 lite", "ici_gbps") == 200.0
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("cpu", "hbm_gbps")


def test_a_reused_device_array_is_refused():
    import jax.numpy as jnp

    guard = SendOnce()
    first, second = jnp.arange(8), jnp.arange(8)
    guard.claim(first)
    guard.claim(second)
    with pytest.raises(ReusedArray, match="already sent"):
        guard.claim(first)

    class Fetched:
        _npy_value = object()

    with pytest.raises(ReusedArray, match="host copy"):
        guard.claim(Fetched())


def test_a_compilation_inside_the_window_fails_the_run(tiny, monkeypatch):
    import jax
    import jax.numpy as jnp

    driver = tiny.driver("mesh_exchange")
    real = driver.run

    def compiling(ctx):
        ev = real(ctx)
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(3))
        ev.compiles_in_window = ctx.compiles.count
        return ev

    monkeypatch.setattr(tiny, "driver", lambda _name: driver)
    monkeypatch.setattr(driver, "run", compiling)
    with pytest.raises(RuntimeError, match="inside the measured window"):
        _rehearse(tiny, "mesh_nton.exchange64M")
