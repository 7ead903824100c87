"""Device kernel: the pool programs' share of their HBM roofline in the
traced part of the window of a hybrid cache's restores: the bytes they
have to move (benchmark/work_kv_prefix_linear.py, from the pages and
states the driver counted while the profiler ran) over their device time
(the modules line: `jit_bm_kvl_produce`, `jit_bm_kvl_state_produce`,
`jit_kv_read_pages`, `jit_kv_write_pages`, and `jit_kv_read_page` /
`jit_kv_write_page`, which run on the state pool alone inside the
window), against the chip's peak.  The programs run on 64, 16, 4 or 1
pages, so the counts come from the driver and not from the runs.  HBM
bandwidth is the bound: none does arithmetic to speak of.  A program
without such modules reads nothing."""

from benchmark import peaks, trace_reduce, work_kv_prefix_linear as work

UNIT = "%"
DRIVERS = ("kv_prefix_linear",)
# The names end at the "(" of the module's id, so that `read_page` does
# not also count `read_pages`.
MODULES = (r"^jit_bm_kvl_produce\(", r"^jit_bm_kvl_state_produce\(",
           r"^jit_kv_read_pages\(", r"^jit_kv_write_pages\(",
           r"^jit_kv_read_page\(", r"^jit_kv_write_page\(")


def read(ev):
    notes = getattr(ev, "notes", {})
    counted = notes.get("traced")
    if ev.trace is None or not counted:
        return None
    seconds = sum(trace_reduce.seconds_by_name(
        ev.trace, trace_reduce.MODULE_LINE, module) for module in MODULES)
    if not seconds:
        return None
    page, state = notes["page_bytes"], notes["state_bytes"]
    moved = (work.produce_hbm_bytes(counted["pages_produced"],
                                    counted["produce_runs"], page)
             + work.state_produce_hbm_bytes(counted["state_pages"],
                                            counted["state_runs"], page,
                                            state)
             + work.read_pages_hbm_bytes(counted["pages_read"], page)
             + work.write_pages_hbm_bytes(counted["pages_written"], page)
             + work.read_state_hbm_bytes(counted["states_read"], state)
             + work.write_state_hbm_bytes(counted["states_written"], state))
    return 100.0 * moved / seconds / (
        peaks.peak(ev.device_kind, "hbm_gbps") * 1e9)
