"""Device kernel: the chunk's produce program's share of its HBM
roofline: the bytes the program has to move over its device time
(`jit_bm_strm_produce` on the modules line), against the chip's peak.
At a chunk's width what the program has to move is the chunk read once
and the next chunk written once: the compiler keeps everything between
(the `echo_fused` pass's copy, its relayouts, the yardstick's add) in
on-chip memory (`S(1)` on every intermediate of the recorded trace), so
`work.produce_hbm_bytes`, which counts both passes through HBM as they go
at 64 MB, would read 112 % here (my chip run, PR 33)."""

from benchmark import peaks, trace_reduce

UNIT = "%"
DRIVERS = ("stream_echo",)
MODULE = r"^jit_bm_strm_produce"


def program_hbm_bytes(chunk_bytes: int) -> int:
    """One read of the chunk before and one write of the chunk made."""
    return 2 * chunk_bytes


def read(ev):
    if ev.trace is None:
        return None
    s = trace_reduce.seconds_per_event(
        ev.trace, trace_reduce.MODULE_LINE, MODULE)
    if not s:
        return None
    achieved = program_hbm_bytes(ev.bytes_per_call) / s
    return 100.0 * achieved / (peaks.peak(ev.device_kind, "hbm_gbps") * 1e9)
