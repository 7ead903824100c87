"""The four phase metrics against the benchmark's own `wire` span, in the
CPU rehearsal of `echo_tcp.small1K`.  The native stamps run from the entry
of `trpc_batch_submit` to the poll that hands a call out, the span from
`pipe.submit` returning to `pipe.poll` returning: the same interval but
for the submit crossing itself, which the benchmark times as its `submit`
span (3 % of a call here, 0.3 % on the chip).  Nothing here is a
measurement."""

import json

from benchmark import peaks
from test_rehearsal import ROOT, _rehearse, tiny  # noqa: F401  (fixture)

PHASES = ("call_queue_us", "call_wire_us", "call_land_us", "call_ready_us")


def test_the_four_phases_sum_to_the_wire_spans_mean(
        tiny, tmp_path, monkeypatch):  # noqa: F811
    # As in the rehearsal's traced test: the recorded trace is a v5e's.
    table = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    table["cpu"] = table["TPU v5 lite"]
    (tmp_path / "peaks.json").write_text(json.dumps(table))
    monkeypatch.setattr(peaks, "_TABLE", tmp_path / "peaks.json")
    result, notes = _rehearse(tiny, "echo_tcp.small1K", trace=True)
    assert result["correct"] is True and result["failed"] == 0
    spans = next(n for n in notes if n["note"] == "spans")
    wire, submit = spans["wire"], spans["submit"]
    wire_mean_us = wire["total_s"] / wire["n"] * 1e6
    submit_mean_us = submit["total_s"] / submit["n"] * 1e6
    values = [result["metrics"][name]["value"] for name in PHASES]
    assert all(v >= 0 for v in values)
    assert (abs(sum(values) - (wire_mean_us + submit_mean_us))
            <= 0.05 * wire_mean_us)
    counted = next(n for n in notes if n["note"] == "counters")
    assert counted["batch_calls_polled"] >= wire["n"]
    assert "batch_calls_failed" not in counted    # zero deltas are left out
    assert result["metrics"]["calls_per_submit"]["value"] >= 1
    assert result["metrics"]["submit_native_us_per_call"]["value"] > 0
