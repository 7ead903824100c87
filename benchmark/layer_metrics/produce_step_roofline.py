"""Device kernel: the produce program's share of its HBM roofline, where
the echo step is the kernel (`echo_fused`): the bytes the program has to
move (work.produce_hbm_bytes) over its device time (`jit_bm_produce` on
the modules line), against the chip's peak.  HBM bandwidth is the bound;
the program does no arithmetic to speak of.  Taken over the whole
program and not the kernel's op alone: the compiler keeps one side of
the kernel in on-chip memory, so the op by itself reads above the peak,
and what S3 can remove are the relayout passes round it.  Where the step
is not the kernel XLA fuses the passes and the bytes are not these:
nothing is read."""

from benchmark import peaks, trace_reduce, work

UNIT = "%"
DRIVERS = ("served_echo",)
MODULE = r"^jit_bm_produce"


def read(ev):
    if ev.trace is None or ev.notes.get("device_step") != "echo_fused":
        return None
    s = trace_reduce.seconds_per_event(
        ev.trace, trace_reduce.MODULE_LINE, MODULE)
    if not s:
        return None
    achieved = work.produce_hbm_bytes(ev.bytes_per_call) / s
    return 100.0 * achieved / (peaks.peak(ev.device_kind, "hbm_gbps") * 1e9)
