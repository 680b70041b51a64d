"""Host<->device copies per answered query: the executor's ``transfer``
(the batch's arrays to the device, inside ``pack``) and ``fetch`` (the
step's outputs to the host, inside ``decode``) spans, summed, over the
queries the window answered, in µs. Its cells (open-loop) are listed
under the metric's ``workloads``."""

from span_sums import TRANSFER_SPANS, us_per_query


def read(run):
    return us_per_query(run, TRANSFER_SPANS)
