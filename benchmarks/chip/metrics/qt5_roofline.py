"""Roofline share of the QT5 serve step: the least time its work could
take at the chip's peaks (``kernels/qt5.py``: bytes over HBM bandwidth)
over the device time of the QT5 executables' runs in the trace (QT3/QT4
batches that ride them included)."""


def read(run):
    dev = run.device
    if dev is None or run.peaks is None or not dev.family_s.get("qt5"):
        return None
    work = run.kernel_work("qt5")
    nbytes = sum(work(B, L, run.serve)["bytes"] for B, L in dev.family_runs["qt5"])
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / dev.family_s["qt5"]
