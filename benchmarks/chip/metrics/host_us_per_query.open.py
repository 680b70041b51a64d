"""Executor host work per answered query: the ``pack``, ``compress`` and
``decode`` spans of the window's batches, summed, over the queries the
window answered. Its cells (open-loop) are listed under the metric's
``workloads``."""


def read(run):
    return run.host_us_per_query()
