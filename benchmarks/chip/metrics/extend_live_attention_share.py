"""Share of the query-key pairs the window's extend launches scored that
the causal mask kept live (kv.stats() extend_live_positions over
extend_scored_positions), in %.  None where the program has no such
counters or ran no extend in the window."""


def read(run):
    scored = run.kv_delta.get("extend_scored_positions")
    live = run.kv_delta.get("extend_live_positions")
    if not scored or live is None:
        return None
    return 100.0 * live / scored
