"""Device kernel: the pool programs' share of their HBM roofline in the
traced part of the window: the bytes they have to move
(benchmark/work_kv_prefix.py, from the pages the driver counted while
the profiler ran) over their device time (the modules line:
`jit_bm_kvp_produce`, `jit_kv_read_pages`, `jit_kv_write_pages`),
against the chip's peak.  The programs run on 16, 4 or 1 pages, so
the pages come from the driver's count and not from the runs.  HBM
bandwidth is the bound: none does arithmetic to speak of.  A program
without such modules reads nothing."""

from benchmark import peaks, trace_reduce, work_kv_prefix

UNIT = "%"
DRIVERS = ("kv_prefix",)
# The names end at the "(" of the module's id.
MODULES = (r"^jit_bm_kvp_produce\(", r"^jit_kv_read_pages\(",
           r"^jit_kv_write_pages\(")


def read(ev):
    counted = getattr(ev, "notes", {}).get("traced")
    if ev.trace is None or not counted:
        return None
    seconds = sum(trace_reduce.seconds_by_name(
        ev.trace, trace_reduce.MODULE_LINE, module) for module in MODULES)
    if not seconds:
        return None
    block = ev.notes["block_bytes"]
    moved = (work_kv_prefix.produce_hbm_bytes(
        counted["pages_produced"], counted["produce_runs"], block)
        + work_kv_prefix.read_pages_hbm_bytes(counted["pages_read"], block)
        + work_kv_prefix.write_pages_hbm_bytes(counted["pages_written"],
                                               block))
    return 100.0 * moved / seconds / (
        peaks.peak(ev.device_kind, "hbm_gbps") * 1e9)
