"""What the per-layer readers of the drain's own spans share: the time
a run's service spans of some names took, over the queries the window
answered, and the host->device byte counter.

A program that records none of the spans, or not the counter, has
nothing to read: the readers then return None."""

TRANSFER_SPANS = ("transfer", "fetch")  # the host<->device copies
SERVICE_SPANS = ("plan", "group", "schedule", "respond", "stats")
DRAIN_WORK = ("schedule", "respond", "stats")  # the spans that name it
H2D_BYTES = "serve.transfer.h2d_bytes"


def us_per_query(run, names, need=None):
    """The spans called ``names``, summed, over the answered queries, in
    µs; None when nothing was answered or no span called one of ``need``
    (default: ``names``) was recorded."""
    need = names if need is None else need
    if run.answered == 0:
        return None
    spent, seen = 0.0, False
    for name, _t0, dur, _depth, _args in run.spans:
        if name in names:
            spent += dur
            seen = seen or name in need
    return 1e6 * spent / run.answered if seen else None


def bytes_per_query(run, counter=H2D_BYTES):
    """The counter's change over the window, over the answered queries."""
    sent = run.counters.get(counter)
    if sent is None or run.answered == 0:
        return None
    return sent / run.answered
