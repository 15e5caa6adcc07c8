"""90th percentile over the requests due in the window of each one's time
per output token after the first, in ms."""
import readers


def read(run):
    v = readers.p90(readers.tpot_s(r, run.giveup) for r in run.requests)
    return None if v is None else 1e3 * v
