"""Prefix store: of the window's `KvReg.Match` answers, the share that
carried a state at the deepest boundary of the matched run and whose
state was served (counters `kv_prefix_match_states` less
`kv_prefix_match_states_shallow`, at most `kv_prefix_state_fetch_total`,
over `kv_prefix_match_total`): 100 where every turn restores its whole
cached context; a state dropped or missing at the run's end, and so a
shallower hit, lowers it.  A program without the counters reads
nothing."""

UNIT = "%"
DRIVERS = ("kv_prefix_linear",)


def read(ev):
    answers = ev.counters.get("kv_prefix_match_total")
    if not answers or "kv_prefix_match_states" not in ev.counters:
        return None
    deepest = (ev.counters["kv_prefix_match_states"]
               - ev.counters.get("kv_prefix_match_states_shallow", 0.0))
    served = ev.counters.get("kv_prefix_state_fetch_total", 0.0)
    return 100.0 * min(deepest, served) / answers
