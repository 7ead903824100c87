"""Mesh plane: the bytes that leave a chip in one exchange over the
all-to-all's device time, against the chip's published interconnect peak
(all four ports; a 2x2 host wires two of them, see peaks.json)."""

from benchmark import peaks, trace_reduce

UNIT = "%"
DRIVERS = ("mesh_exchange",)
OPS = r"all-to-all"


def read(ev):
    if ev.trace is None:
        return None
    s = trace_reduce.seconds_per_span(
        ev.trace, trace_reduce.OP_LINE, OPS, "exchange")
    if not s:
        return None
    return 100.0 * (ev.bytes_per_call / s) / (
        peaks.peak(ev.device_kind, "ici_gbps") * 1e9)
