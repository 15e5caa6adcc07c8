"""90th percentile of time to first token over every request due in the
window, from its due time, in ms."""
import readers


def read(run):
    v = readers.p90(readers.ttft_s(r, run.giveup) for r in run.requests)
    return None if v is None else 1e3 * v
