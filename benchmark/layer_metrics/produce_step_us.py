"""Device kernel: mean device time of the program that makes a request
(`jit_bm_produce` on the modules line): the device plane's echo step and,
in the same program, the yardstick's add of the checksum to every
element.  Under 1 MB XLA fuses that add into the step's own pass; from
1 MB the step is a kernel with a relayout on each side and the add is a
fourth pass.  The kernel alone is the `custom-call` among the traced
run's `device_ops`."""

from benchmark import trace_reduce

UNIT = "us"
DRIVERS = ("served_echo",)
MODULE = r"^jit_bm_produce"


def read(ev):
    if ev.trace is None:
        return None
    s = trace_reduce.seconds_per_event(
        ev.trace, trace_reduce.MODULE_LINE, MODULE)
    return None if s is None else s * 1e6
