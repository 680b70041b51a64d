"""Roofline share of the QT5 serve step: the least time its work could
take at the cell's peaks (``kernels/qt5.py``: bytes over the HBM
bandwidth of all the devices the trace holds) over the device time of
the QT5 executables' runs in the trace (one device's, the mean over
them; QT3/QT4 batches that ride them included)."""


def read(run):
    dev = run.device
    if dev is None or run.peaks is None or not dev.family_s.get("qt5"):
        return None
    work = run.kernel_work("qt5")
    nbytes = sum(work(B, L, run.serve)["bytes"] for B, L in dev.family_runs["qt5"])
    bandwidth = dev.n_devices * run.peaks["hbm_bytes_per_s"]
    return 100.0 * nbytes / bandwidth / dev.family_s["qt5"]
