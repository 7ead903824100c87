"""BENCHMARK.json against the contract's shape, and the harness's promise
that a later PR adds a configuration, a mix, a reader and a driver of
its own, with its rehearsal sizes and its recorded trace, as files and
entries, editing nothing that is there."""

import json
import pathlib
import re
import shutil

import pytest

from benchmark.manifest import Manifest, ManifestError
from test_rehearsal import (RESULT_KEYS, _rehearse, copy_tree,
                            recorded_trace, shrink_traffic)

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


def test_top_level_keys_and_limits(manifest):
    doc = manifest.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 << 10
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert 1 <= len(doc["paths"]) <= 16
    assert len(doc["command"]) <= 32
    for word in doc["command"]:
        assert not word.startswith("/") and ".." not in word
    assert any(word.startswith(doc["paths"][0] + "/")
               for word in doc["command"])
    for path in doc["paths"]:
        assert (ROOT / path).is_dir()


def test_every_name_resolves_to_its_files(manifest):
    doc = manifest.doc
    used = set()
    for name in manifest.cell_names():
        cell = manifest.cell(name)
        used.add(cell.config_name)
        assert hasattr(manifest.driver(cell.driver_name), "run")
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2
    assert used == {c["name"] for c in doc["configs"]}
    files = [c["file"] for c in doc["configs"]]
    assert len(set(files)) == len(files)
    for c in doc["configs"]:
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert "guarantee" in body and "loopback" in body
        assert set(body["reduced"]) <= set(body)
        # The plain reference beside the configuration is there.
        file, function = body["reference"].split("::")
        assert f"def {function}(" in (ROOT / file).read_text()
    for m in doc["per_layer"]:
        reader = manifest.reader(m["name"])
        assert callable(reader.read) and reader.UNIT == m["unit"]
        # No cell of a later PR inherits a per-layer metric unasked.
        assert m.get("workloads"), f"{m['name']} lists no cells"
    # What the CPU rehearsal needs of a mix and of a driver is there, so
    # that the next PR learns of a missing file here, from a message.
    for path in sorted((manifest.home / "traffic").glob("*.json")):
        assert isinstance(json.loads(path.read_text()).get("rehearsal"),
                          dict), f"{path.name} has no `rehearsal` object"
    for path in sorted((manifest.home / "drivers").glob("*.py")):
        assert recorded_trace(manifest, path.stem).is_file()


def test_names_units_and_entries_use_only_what_the_contract_allows(manifest):
    doc = manifest.doc
    keys = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
    }
    for section, allowed in keys.items():
        names = [e["name"] for e in doc[section]]
        assert len(set(names)) == len(names)
        for e in doc[section]:
            assert allowed <= set(e) <= allowed | (
                {"workloads"} if "unit" in allowed else set()), e
            assert NAME.match(e["name"]), e["name"]
            for text in ("why", "layer", "source"):
                if text in e:
                    assert 1 <= len(e[text]) <= 200
                    assert "\n" not in e[text] and "\t" not in e[text]
    metrics = doc["end_to_end"] + doc["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for cell in m.get("workloads", []):
            assert cell in manifest.cell_names()
    for m in doc["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for w in doc["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert 2 <= len(pairs) <= 24
    for c in doc["configs"]:
        assert len(c["reduced"]) <= 16


def test_at_most_half_the_cells_ask_for_four_chips(manifest):
    cells = manifest.doc["workloads"]
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)


def test_every_moves_names_a_metric_of_a_cell_its_readers_driver_runs(
        manifest):
    cells = [manifest.cell(n) for n in manifest.cell_names()]
    reported = set()
    for m in manifest.doc["per_layer"]:
        reader = manifest.reader(m["name"])
        homes = [c for c in cells if m in c.per_layer]
        assert homes, f"{m['name']} is reported in no cell"
        for c in homes:
            # None: the reader reads what every driver hands back.
            assert (reader.DRIVERS is None
                    or c.driver_name in reader.DRIVERS), (m["name"], c.name)
            assert m["moves"] in {e["name"] for e in c.end_to_end}
            reported.add((m["name"], c.name))
    # Layer names are the same letter for letter within a layer.
    layers = {m["layer"] for m in manifest.doc["per_layer"]}
    assert len({name.lower() for name in layers}) == len(layers)


def _files(root) -> dict:
    return {p: p.read_bytes()
            for p in (root / "benchmark").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_config_a_mix_and_a_reader_dropped_in_are_found(tmp_path):
    copy_tree(tmp_path)
    before = _files(tmp_path)
    home = tmp_path / "benchmark"
    config = json.loads((home / "configs" / "echo_tcp.json").read_text())
    config["channel"] = {"connection_type": "pooled"}
    (home / "configs" / "echo_tcp_pooled.json").write_text(
        json.dumps(config))
    mix = json.loads((home / "traffic" / "small1K.json").read_text())
    mix["payload_bytes"] = 4096
    (home / "traffic" / "small4K.json").write_text(json.dumps(mix))
    (home / "layer_metrics" / "calls_counted.py").write_text(
        'UNIT = "calls"\nDRIVERS = ("served_echo",)\n\n\n'
        "def read(ev):\n    return float(len(ev.call_s))\n")
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "echo_tcp_pooled", "source": config["source"],
        "file": "benchmark/configs/echo_tcp_pooled.json", "reduced": [],
        "why": "pooled connections"})
    doc["workloads"].append({
        "name": "echo_tcp_pooled.small4K", "config": "echo_tcp_pooled",
        "traffic": "small4K", "chips": 1, "why": "a later PR's cell"})
    doc["per_layer"].append({
        "name": "calls_counted", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "Served path, tail",
        "moves": "call_p50", "workloads": ["echo_tcp_pooled.small4K"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    grown = Manifest(tmp_path)
    cell = grown.cell("echo_tcp_pooled.small4K")
    assert cell.traffic["payload_bytes"] == 4096
    assert cell.config["channel"] == {"connection_type": "pooled"}
    assert cell.driver_name == "served_echo"
    # It gets the end-to-end metrics that list no cells, and per layer
    # only what lists it: every per-layer entry names its cells.
    assert {m["name"] for m in cell.per_layer} == {"calls_counted"}
    assert {m["name"] for m in cell.end_to_end} == {"call_p50", "setup_s"}
    assert grown.reader("calls_counted").UNIT == "calls"
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"


TOY_DRIVER = '''"""Driver `toy_copy`: one jitted device copy per call."""
import time

from benchmark.evidence import Evidence
from benchmark.payload import seeded_bits
from benchmark.reference import echo_reference


def run(ctx) -> Evidence:
    import jax
    import jax.numpy as jnp

    mix, now = ctx.cell.traffic, time.perf_counter
    copy = jax.jit(lambda x: x | jnp.uint32(0))
    sent = seeded_bits(ctx.seed, (int(mix["payload_bytes"]) // 4,))
    for _ in range(int(mix["warm_calls"])):
        got = jax.block_until_ready(copy(sent))
    compiles_before = ctx.compiles.count
    calls, traced_from = [], None
    t_open = now()
    while not calls or calls[-1][0] < t_open + ctx.seconds:
        if (ctx.trace and traced_from is None and calls and calls[-1][0]
                >= t_open + ctx.seconds - float(mix["trace_seconds"])):
            ctx.start_trace()
            traced_from = now()
        t0 = now()
        with ctx.spans.span("copy"):
            got = jax.block_until_ready(copy(sent))
        calls.append((now(), now() - t0))
    compiles_in_window = ctx.compiles.count - compiles_before
    if traced_from is not None:
        ctx.stop_trace()
    failed = int(jnp.any(got != echo_reference(sent)))
    return Evidence(
        t_open=t_open, t_close=calls[-1][0],
        call_s=[s for _, s in calls], call_end=[end for end, _ in calls],
        bytes_per_call=int(mix["payload_bytes"]), attempted=len(calls),
        failed=failed, correct=not failed,
        compiles_in_window=compiles_in_window, spans=ctx.spans, counters={},
        traced=traced_from and (traced_from, calls[-1][0]))
'''


def test_a_driver_of_its_own_dropped_in_is_rehearsed_untraced_and_traced(
        tmp_path):
    """What the next `model_config` PR does: a driver, a configuration
    that names it, a mix with its rehearsal sizes, a reader bound to the
    driver, a recorded trace under the driver's name, and the entries;
    no file that was there is edited."""
    copy_tree(tmp_path)
    before = _files(tmp_path)
    home = tmp_path / "benchmark"
    (home / "drivers" / "toy_copy.py").write_text(TOY_DRIVER)
    (home / "configs" / "toy.json").write_text(json.dumps({
        "source": "a later PR's deployment", "driver": "toy_copy",
        "reference": "benchmark/reference.py::echo_reference",
        "reduced": [], "guarantee": "a copy is byte-exact",
        "loopback": True}))
    (home / "traffic" / "copy64M.json").write_text(json.dumps({
        "why": "one copy at a time", "payload_bytes": 64 << 20,
        "warm_calls": 16, "trace_seconds": 2,
        "rehearsal": {"payload_bytes": 4096, "warm_calls": 2}}))
    (home / "layer_metrics" / "copy_us.py").write_text(
        'from benchmark import stats\n\nUNIT = "us"\n'
        'DRIVERS = ("toy_copy",)\n\n\ndef read(ev):\n'
        '    took = ev.spans.durations("copy", ev.t_open, ev.t_close)\n'
        "    return stats.median(took) * 1e6 if took else None\n")
    shutil.copy(recorded_trace(Manifest(ROOT), "served_echo"),
                home / "testdata" / "trace_toy_copy.json.gz")
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "toy", "source": "a later PR's deployment",
        "file": "benchmark/configs/toy.json", "reduced": [],
        "why": "a driver of its own"})
    doc["workloads"].append({
        "name": "toy.copy64M", "config": "toy", "traffic": "copy64M",
        "chips": 1, "why": "a later PR's cell"})
    next(m for m in doc["end_to_end"]
         if m["name"] == "goodput")["workloads"].append("toy.copy64M")
    next(m for m in doc["per_layer"] if m["name"] == "device_idle_share")[
        "workloads"].append("toy.copy64M")
    doc["per_layer"].append({
        "name": "copy_us", "unit": "us", "better": "lower",
        "source": "program_span", "layer": "Device kernel",
        "moves": "call_p50", "workloads": ["toy.copy64M"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    after_adding = _files(tmp_path)
    shrink_traffic(tmp_path)   # the rehearsal's own, as the fixture's

    grown = Manifest(tmp_path)
    cell = grown.cell("toy.copy64M")
    assert cell.driver_name == "toy_copy"
    assert cell.traffic["payload_bytes"] == 4096
    result, notes = _rehearse(grown, "toy.copy64M")
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"goodput", "call_p50", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(next(n for n in notes if n["note"] == "spans")) == {
        "note", "copy"}
    traced, _ = _rehearse(grown, "toy.copy64M", trace=True)
    assert set(traced) == RESULT_KEYS | {"breakdown"}
    assert traced["correct"] is True
    # A reader bound to the driver, and one that names no driver.
    assert set(traced["metrics"]) == {"copy_us", "device_idle_share"}
    assert traced["metrics"]["copy_us"]["value"] > 0
    assert traced["device"]["busy_s"] > 0
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    # A reader bound to another driver is refused by name, not run.
    next(m for m in doc["per_layer"] if m["name"] == "h2d_rate")[
        "workloads"].append("toy.copy64M")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(ManifestError, match="not 'toy_copy'"):
        _rehearse(Manifest(tmp_path), "toy.copy64M", trace=True)
    for path, content in before.items():
        assert after_adding[path] == content, f"{path} was edited"


def test_a_name_that_resolves_to_nothing_says_which(manifest, tmp_path):
    with pytest.raises(ManifestError, match="no workload 'nope'"):
        manifest.cell("nope")
    with pytest.raises(ManifestError, match="per-layer metric 'absent'"):
        manifest.reader("absent")
