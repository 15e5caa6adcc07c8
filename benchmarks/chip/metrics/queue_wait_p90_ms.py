"""90th percentile of due time to admission into a slot (the ticket's
t_admit), over the requests due in the window, in ms."""
import readers


def read(run):
    v = readers.p90(readers.queue_wait_s(r, run.giveup) for r in run.requests)
    return None if v is None else 1e3 * v
