"""The service drain's own host work per answered query: its ``plan``,
``group``, ``schedule`` (work horizon and EDF splitting),
``respond`` (resolving each unit's tickets) and ``stats`` spans,
summed, over the queries the window answered, in µs. Its cells
(closed-loop) are listed under the metric's ``workloads``."""

from span_sums import DRAIN_WORK, SERVICE_SPANS, us_per_query


def read(run):
    return us_per_query(run, SERVICE_SPANS, need=DRAIN_WORK)
