"""Share of the traced window in which no operation ran on the device:
1 - (union of device-operation intervals / window), from the profiler
trace. Its cells (open-loop) are listed under the metric's
``workloads``."""


def read(run):
    return None if run.device is None else run.device.idle_pct()
