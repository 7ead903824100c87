"""Device kernel: the page programs' share of their HBM roofline: the
bytes they have to move over their device time (the modules line),
against the chip's peak.  The produce program (`jit_bm_kv_produce`)
reads the page before and writes the new one into the pool's slot and
out as its second result; `jit_kv_read_page` reads a slot and writes
the page; `jit_kv_write_page` reads the page and writes the slot.  HBM
bandwidth is the bound: none does arithmetic to speak of."""

from benchmark import peaks, trace_reduce

UNIT = "%"
DRIVERS = ("kv_pull",)
# Pages read or written in HBM by one run of each program.
PAGES_MOVED = {r"^jit_bm_kv_produce": 3, r"^jit_kv_read_page": 2,
               r"^jit_kv_write_page": 2}


def page_hbm_bytes(page_bytes: int, pages_moved: int) -> int:
    return page_bytes * pages_moved


def read(ev):
    if ev.trace is None:
        return None
    moved = seconds = 0.0
    for module, pages in PAGES_MOVED.items():
        runs = trace_reduce.count_by_name(
            ev.trace, trace_reduce.MODULE_LINE, module)
        moved += runs * page_hbm_bytes(ev.bytes_per_call, pages)
        seconds += trace_reduce.seconds_by_name(
            ev.trace, trace_reduce.MODULE_LINE, module)
    if not seconds:
        return None
    return 100.0 * moved / seconds / (
        peaks.peak(ev.device_kind, "hbm_gbps") * 1e9)
