"""Trace reduction on a profiler trace recorded on a TPU v5e, the metric
readers that use it, and each kernel's work count at a small shape.

``data/trace_small_md9`` holds the trace of a 0.5 s window of
``md9-mixed-closed``, recorded by ``record_trace.py``: the
``.xplane.pb``, gzipped, and the host side (the window's anchor, the
service's spans, the loop's states) as ``host.json``."""

import bisect
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

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


# the readers' values on the recorded trace before they divided by the
# device count (one chip): a one-chip trace must read the same
ONE_CHIP_ROOFLINE = {"qt1_roofline": 0.020250277613556196,
                     "qt5_roofline": 0.008428820200148601}


def _run_data(dt, cell_name="md9-mixed-closed"):
    cell = run.resolve_cell(run.load_benchmark(), cell_name, True)
    return run.RunData(cell=cell, setup_s=1.0, loop="closed", window_s=dt.window_s,
                       attempted=1, answered=1, latencies_s=[], queue_s=[],
                       counters={}, device=dt, peaks=run.peaks_for("TPU v5 lite"),
                       serve=cell.config["serve"])


def test_rooflines_unchanged_at_one_chip(recorded):
    dt, _ = recorded
    assert dt.n_devices == 1
    data = _run_data(dt)
    for name, want in ONE_CHIP_ROOFLINE.items():
        assert run.load_module("metrics", name).read(data) == pytest.approx(want, rel=1e-12)


def _ev(name, a_ns, b_ns):
    return SimpleNamespace(name=name, start_ns=a_ns, duration_ns=b_ns - a_ns)


def _line(name, events):
    return SimpleNamespace(name=name, events=events)


def _synthetic(n_planes, n_batches=5, step_s=0.010, run_s=0.004):
    """A window of ``n_batches`` qt5 batches (B 8, L 1024), each run as
    one executable of ``run_s`` on every one of ``n_planes`` devices, as
    one program sharded over them runs. Returns the profile, the
    window's anchor, the spans and the states."""
    t_window, w0_ns = 100.0, 10**9  # perf_counter 100.0 s = profiler 1 s
    to_ns = lambda t: int(round((t - t_window) * 1e9)) + w0_ns  # noqa: E731
    spans, states, modules, ops = [], [], [], []
    for k in range(n_batches):
        t0 = t_window + 0.001 + k * step_s
        states.append(("drain", t0, t0 + 0.009))
        spans.append(("batch", t0, 0.008, 1,
                      {"family": "qt5", "B": 8, "bucket": 1024, "n": 8}))
        a = to_ns(t0 + 0.002)
        modules.append(_ev("jit_step(1)", a, a + int(run_s * 1e9)))
        ops.append(_ev("%fusion.1 = s32[8] fusion()", a, a + int(run_s * 1e9)))
    host = SimpleNamespace(name="/host:CPU", lines=[_line("python", [
        _ev("bench.window", w0_ns, to_ns(t_window + n_batches * step_s))])])
    planes = [host] + [
        SimpleNamespace(name=f"/device:TPU:{i}",
                        lines=[_line("XLA Modules", modules), _line("XLA Ops", ops)])
        for i in range(n_planes)]
    return SimpleNamespace(planes=planes), t_window, spans, states


@pytest.mark.parametrize("n", [1, 2, 4])
def test_rooflines_are_shares_of_the_devices_combined_peak(n):
    """On n device planes each batch is counted once, the family's
    device time is one device's, and the roofline is the batch work over
    n devices' bandwidth: 1/n of what the same runs read on one."""
    pd, t_window, spans, states = _synthetic(n)
    dt = devtrace.reduce_profile(pd, t_window, spans, states)
    assert dt.n_devices == n
    assert dt.family_runs == {"qt5": [(8, 1024)] * 5}
    assert dt.family_s["qt5"] == pytest.approx(5 * 0.004)
    assert dt.busy_s == pytest.approx(5 * 0.004)
    data = _run_data(dt)
    work = run.load_module("kernels", "qt5").work(8, 1024, data.serve)["bytes"]
    want = 100.0 * 5 * work / (n * data.peaks["hbm_bytes_per_s"]) / (5 * 0.004)
    assert run.load_module("metrics", "qt5_roofline").read(data) == pytest.approx(want)


class _ScanHost:
    """The host labels as first computed: for each instant, a scan back
    over every interval that starts within the window's longest span."""

    def __init__(self, spans, states):
        ivs = [(t0, t0 + dur, depth, name) for name, t0, dur, depth, _a in spans]
        ivs += [(t0, t1, -1, name) for name, t0, t1 in states]
        self.ivs = sorted(ivs)
        self.starts = [iv[0] for iv in self.ivs]
        self.max_dur = max((b - a for a, b, _d, _n in self.ivs), default=0.0)

    def label(self, t):
        k = bisect.bisect_right(self.starts, t)
        best = None
        while k > 0:
            k -= 1
            a, b, depth, name = self.ivs[k]
            if a < t - self.max_dur:
                break
            if a <= t < b and (best is None or depth > best[0]):
                best = (depth, name)
        if best is None:
            return "outside"
        if best[0] < 0:
            return best[1]
        return best[1] if best[1] == "drain" else f"drain/{best[1]}"


def _stalled_window():
    """Drains of 10 ms with their children, one of which stalls 3 s in
    ``execute``, and spans that start together at one depth."""
    spans, states, t = [], [], 0.0
    for k in range(40):
        d = 3.0 if k == 17 else 0.004
        states += [("submit", t, t + 0.001), ("drain", t + 0.001, t + 0.006 + d)]
        spans += [("drain", t + 0.001, 0.005 + d, 0, {}),
                  ("plan", t + 0.001, 0.001, 1, {}),
                  ("batch", t + 0.002, 0.003 + d, 1, {}),
                  ("pack", t + 0.002, 0.001, 2, {}),
                  ("transfer", t + 0.002, 0.0005, 3, {}),
                  ("execute", t + 0.003, 0.001 + d, 2, {}),
                  ("dispatch", t + 0.003, 0.0002, 3, {}),
                  ("compile", t + 0.003, 0.0002, 3, {})]
        t += 0.007 + d
        states.append(("wait", t - 0.0005, t))
    return spans, states


def _instants(spans, states, rng):
    edges = sorted({x for _n, t0, dur, _d, _a in spans for x in (t0, t0 + dur)}
                   | {x for _n, t0, t1 in states for x in (t0, t1)})
    mids = [(a + b) / 2 for a, b in zip(edges, edges[1:])]
    lo, hi = edges[0] - 0.01, edges[-1] + 0.01
    return edges + mids + list(rng.uniform(lo, hi, 5000)) + [lo, hi]


@pytest.mark.parametrize("window", ["recorded", "stalled"])
def test_host_labels_match_the_scan(window):
    if window == "recorded":
        host = json.loads((DATA / "host.json").read_text())
        spans, states = host["spans"], host["states"]
    else:
        spans, states = _stalled_window()
    new, old = devtrace._Host(spans, states), _ScanHost(spans, states)
    for t in _instants(spans, states, np.random.default_rng(7)):
        assert new.label(t) == old.label(t), t
