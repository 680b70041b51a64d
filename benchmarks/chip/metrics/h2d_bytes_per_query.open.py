"""Bytes sent to the device per answered query: the change of the
service's ``serve.transfer.h2d_bytes`` counter over the window (the
``nbytes`` of every array the executor copies to the device), over
the queries the window answered. Its cells (open-loop) are listed
under the metric's ``workloads``."""

from span_sums import bytes_per_query


def read(run):
    return bytes_per_query(run)
