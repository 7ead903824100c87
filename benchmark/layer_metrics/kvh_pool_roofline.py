"""Device kernel: the pool programs' share of their HBM roofline in a
hand-over of a cache of two kinds: the bytes they have to move over
their device time (the modules line), against the chip's peak.

Per sequence, with P the bytes of its pages and S those of its states
(the driver's notes: records times record bytes of each kind):
`jit_bm_kvh_produce` reads the sequence before (P + S), writes the new
one into the slots of both prefill pools (P + S) and out as its results
(P + S); `jit_kv_read_pages` reads the pages' slots and writes them out
(2 P); `jit_kv_read_page`, run on the state pool alone inside the
window, reads a state slot and writes it out (2 S); `jit_kv_write_pages`
reads the landed pages and writes their slots (2 P); `jit_kv_write_page`
the same for the states (2 S).  A program without such modules reads
nothing.  HBM bandwidth is the bound: none does arithmetic to speak
of."""

from benchmark import peaks, trace_reduce

UNIT = "%"
DRIVERS = ("kv_seq_pull",)
# (times the pages' bytes, times the states' bytes) one run of each
# program moves in HBM.  The names end at the "(" of the module's id, so
# that `read_page` does not also count `read_pages`.
MOVED = {r"^jit_bm_kvh_produce\(": (3, 3),
         r"^jit_kv_read_pages\(": (2, 0), r"^jit_kv_read_page\(": (0, 2),
         r"^jit_kv_write_pages\(": (2, 0), r"^jit_kv_write_page\(": (0, 2)}


def program_hbm_bytes(page_bytes: int, state_bytes: int,
                      moved: tuple[int, int]) -> int:
    return page_bytes * moved[0] + state_bytes * moved[1]


def read(ev):
    notes = getattr(ev, "notes", {})
    if ev.trace is None or "snapshot_record_bytes" not in notes:
        return None
    page_bytes = notes["page_records"] * notes["page_record_bytes"]
    state_bytes = notes["snapshot_records"] * notes["snapshot_record_bytes"]
    moved = seconds = 0.0
    for module, times in MOVED.items():
        runs = trace_reduce.count_by_name(
            ev.trace, trace_reduce.MODULE_LINE, module)
        moved += runs * program_hbm_bytes(page_bytes, state_bytes, times)
        seconds += trace_reduce.seconds_by_name(
            ev.trace, trace_reduce.MODULE_LINE, module)
    if not seconds:
        return None
    return 100.0 * moved / seconds / (
        peaks.peak(ev.device_kind, "hbm_gbps") * 1e9)
