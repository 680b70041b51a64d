"""95th percentile of the time from each request's scheduled arrival to
its answer (the return of the drain that served it), over every request
of the window that was answered (host clock)."""

from load import percentile


def read(run):
    if run.loop != "open" or not run.latencies_s:
        return None
    return 1e3 * percentile(run.latencies_s, 95)
