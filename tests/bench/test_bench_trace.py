"""Trace reduction on a profiler trace recorded on a TPU v5e, the metric
readers that use it, and each kernel's work count at a small shape.

``data/trace_small_md9`` holds the trace of a 0.5 s window of
``md9-mixed-closed``, recorded by ``record_trace.py``: the
``.xplane.pb``, gzipped, and the host side (the window's anchor, the
service's spans, the loop's states) as ``host.json``."""

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench_tiny import HARNESS, run

import devtrace  # noqa: E402  (the harness directory is on the path)

DATA = Path(__file__).resolve().parent / "data" / "trace_small_md9"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    host = json.loads((DATA / "host.json").read_text())
    pd = ProfileData.from_serialized_xspace(
        gzip.decompress((DATA / "window.xplane.pb.gz").read_bytes()))
    return devtrace.reduce_profile(pd, host["window"], host["spans"], host["states"]), host


def test_busy_and_idle(recorded):
    dt, host = recorded
    last = max(t0 + dur for _n, t0, dur, _d, _a in host["spans"])
    assert 0 < dt.busy_s < dt.window_s
    assert dt.window_s == pytest.approx(last - host["window"], rel=0.05)
    idle = sum(s for _, s in dt.top_gaps)
    assert dt.busy_s + idle == pytest.approx(dt.window_s, rel=1e-6)


def test_step_time_by_family(recorded):
    dt, host = recorded
    batches = [s for s in host["spans"] if s[0] == "batch" and "B" in s[4]]
    assert dt.unplaced_runs == 0
    assert sum(len(v) for v in dt.family_runs.values()) == len(batches)
    assert {"qt1", "qt5"} <= set(dt.family_s)
    for fam, seconds in dt.family_s.items():
        assert 0 < seconds <= dt.busy_s * 1.0001
    assert sum(dt.family_s.values()) <= dt.window_s


def test_breakdown(recorded):
    dt, _ = recorded
    for rows in (dt.top_ops, dt.top_gaps):
        values = [s for _, s in rows]
        assert values == sorted(values, reverse=True) and values[0] > 0
    assert {name.split("/")[0] for name, _ in dt.top_ops} <= {
        "qt1", "qt2", "qt34", "qt5", "other"}
    states = {name for name, _ in dt.top_gaps}
    assert states <= {"submit", "wait", "drain", "outside"} | {
        f"drain/{p}" for p in ("plan", "group", "batch", "pack", "compress",
                               "compile", "dispatch", "execute", "decode")}


def test_device_readers(recorded):
    dt, _ = recorded
    cell = run.resolve_cell(run.load_benchmark(), "md9-mixed-closed", True)
    data = run.RunData(cell=cell, setup_s=1.0, loop="closed", window_s=dt.window_s,
                       attempted=1, answered=1, latencies_s=[], queue_s=[],
                       counters={}, device=dt, peaks=run.peaks_for("TPU v5 lite"),
                       serve=cell.config["serve"])
    for loop in ("open", "closed"):
        idle = run.load_module("metrics", f"device_idle_share.{loop}").read(data)
        assert idle == pytest.approx(100 * (1 - dt.busy_s / dt.window_s))
    share = run.load_module("metrics", "qt5_roofline").read(data)
    assert 0 < share <= 100
    data.device = None
    assert run.load_module("metrics", "device_idle_share.open").read(data) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.BenchFailure, match="no peaks"):
        run.peaks_for("TPU v99")
    assert "source" in run.peaks_for("TPU v5 lite")


def _out_bytes(B, top_k, L):
    return B * min(top_k, L) * 4 * 4  # (score, g, lo, hi) per result


def test_kernel_work_counts_the_packed_rows():
    """Each family's count equals the bytes of the arrays the program's
    packer hands its step, plus the results the step returns."""
    from repro.core.index_builder import build_index
    from repro.core.jax_search import pack_qt1_batch, pack_qt5_batch
    from repro.core.query import qt5_plan, select_fst_keys

    import corpus as bench_corpus  # noqa: E402

    c = bench_corpus.generate_corpus(120, 60, 2000, 1.1, 25, 60, 3)
    table, lex = run.to_program(c)
    index = build_index(table, lex, max_distance=5)
    serve = json.loads((HARNESS / "configs" / "fiction-md5.json").read_text())["serve"]
    B, L = 2, 256
    stop = bench_corpus.sample_stop_queries(c, B, window=3, seed=1)
    qt1 = pack_qt1_batch(index, stop, L=L, K=serve["k_fst"],
                         plans=[select_fst_keys(q)[1] for q in stop])
    want = sum(np.asarray(a).nbytes for a in qt1.device_args())
    work = run.load_module("kernels", "qt1").work(B, L, serve)
    assert work["bytes"] == want + _out_bytes(B, serve["top_k"], L)
    mixed = [q for q in bench_corpus.sample_mixed_queries(c, 40, window=9, seed=2)
             if qt5_plan(index, q) is not None][:B]
    qt5 = pack_qt5_batch(index, mixed, L=L, Kn=serve["k_ns"], Ks=serve["k_st"])
    want = sum(np.asarray(a).nbytes for a in qt5.device_args())
    work = run.load_module("kernels", "qt5").work(B, L, serve)
    assert work["bytes"] == want + _out_bytes(B, serve["top_k"], L)
    assert work["bound"] == "memory"
