"""95th percentile of the service's ``queue`` phase (submit, or the
scheduled arrival it was backdated to, until its batch started), over
the window's answered requests, from each response's phase record."""

from load import percentile


def read(run):
    if not run.queue_s:
        return None
    return 1e3 * percentile(run.queue_s, 95)
