"""Observability coverage (DESIGN.md §15): the metrics registry and
tracer primitives in isolation, plus the serving integration — span
trees over a mixed five-type drain, per-response phase breakdowns that
tile the end-to-end latency, Chrome-trace export, est-vs-measured cost
calibration, and snapshot hygiene."""

import json

import numpy as np
import pytest

from repro.core.index_builder import build_index
from repro.data.corpus import generate_corpus, sample_typed_queries
from repro.launch.mesh import make_mesh
from repro.obs import Histogram, MetricsRegistry, Tracer, chrome_trace
from repro.serving import SearchService, ServeConfig

D = 5
BUCKETS = (256, 1024)
PHASES = ("queue", "plan", "pack", "compress", "compile", "dispatch",
          "execute", "decode")


@pytest.fixture(scope="module")
def typed_world():
    table, lex = generate_corpus(n_docs=80, mean_doc_len=70, vocab_size=500, seed=11)
    lex.sw_count = 14
    lex.fu_count = 30
    idx = build_index(table, lex, max_distance=D)
    mesh = make_mesh((1, 1), ("data", "model"))
    typed = {
        k: sample_typed_queries(table, lex, 6, k, window=D, seed=3)
        for k in ("qt1", "qt2", "qt3", "qt4", "qt5")
    }
    assert len({k for k in typed if typed[k]}) == 5, "need all five types"
    return idx, mesh, typed


@pytest.fixture(scope="module")
def world(typed_world):
    idx, mesh, typed = typed_world
    mixed = [q for qs in typed.values() for q in qs[:3] if q]
    return idx, mesh, mixed


@pytest.fixture(scope="module")
def served(world):
    """One service drained twice (cold then warm) over a five-type mix."""
    idx, mesh, mixed = world
    svc = SearchService(idx, mesh,
                        ServeConfig(buckets=BUCKETS, max_batch=8, top_k=16))
    rounds = []
    for _ in range(2):
        for q in mixed:
            svc.submit(q)
        rounds.append(svc.drain())
    return svc, mixed, rounds


# -- registry / histogram primitives ---------------------------------------
def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(7)
    vals = rng.lognormal(mean=2.0, sigma=1.5, size=50)
    h = Histogram("t", capacity=64)
    for v in vals:
        h.observe(v)
    for q in (0, 25, 50, 95, 99, 100):
        assert h.percentile(q) == pytest.approx(np.percentile(vals, q), rel=0)
    snap = h.snapshot()
    assert snap["count"] == 50
    assert snap["sum"] == pytest.approx(vals.sum())
    assert snap["min"] == vals.min() and snap["max"] == vals.max()
    for key, q in (("p50", 50), ("p95", 95), ("p99", 99)):
        assert snap[key] == pytest.approx(float(np.quantile(vals, q / 100)))


def test_histogram_ring_keeps_last_capacity_samples():
    h = Histogram("t", capacity=64)
    vals = np.arange(100, dtype=np.float64)
    for v in vals:
        h.observe(v)
    # exact count/min/max survive eviction; percentiles cover the ring
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["min"] == 0.0 and snap["max"] == 99.0
    assert h.percentile(50) == pytest.approx(np.percentile(vals[-64:], 50))
    assert h.percentile(0) == 36.0  # oldest resident sample


def test_registry_get_or_create_and_type_guard():
    reg = MetricsRegistry()
    c = reg.counter("serve.x")
    reg.inc("serve.x", 2)
    assert c.value == 2 and reg.counter("serve.x") is c
    reg.set("serve.g", 3.5)
    reg.observe("serve.h", 1.0)
    with pytest.raises(TypeError):
        reg.histogram("serve.x")
    assert reg.names("serve.") == ["serve.g", "serve.h", "serve.x"]
    snap = reg.snapshot("serve.")
    assert snap["serve.x"] == 2 and snap["serve.g"] == 3.5
    assert snap["serve.h"]["count"] == 1
    json.dumps(snap)  # plain data only


def test_tracer_bounded_and_disabled():
    tr = Tracer(capacity=4)
    for i in range(6):
        with tr.span("s", i=i):
            pass
    spans = tr.snapshot()
    assert len(spans) == 4 and tr.dropped == 2
    assert [s.args["i"] for s in spans] == [2, 3, 4, 5]  # oldest evicted
    off = Tracer(enabled=False)
    with off.span("s") as sp:
        sp.set(k=1)  # null handle accepts args, keeps nothing
        with off.span("t", i=1) as inner:
            pass
    assert off.snapshot() == [] and off.dropped == 0
    # the disabled path allocates nothing: one shared no-op per call
    assert off.span("s") is off.span("u", k=2) is sp is inner


# -- span trees over a mixed five-type drain -------------------------------
def test_span_tree_nesting_and_ordering(served):
    svc, mixed, rounds = served
    spans = svc.tracer.snapshot()
    roots = [s for s in spans if s.depth == 0]
    assert [s.name for s in roots] == ["drain", "drain"]  # one tree per drain
    # nesting invariant: every non-root span is contained in time by
    # exactly the spans one level up that Perfetto would nest it under
    for s in spans:
        if s.depth == 0:
            continue
        parents = [p for p in spans
                   if p.depth == s.depth - 1 and p.tid == s.tid
                   and p.ts <= s.ts and s.end <= p.end]
        assert parents, f"orphan span {s.name} at depth {s.depth}"
    # siblings under one root never overlap, and snapshot order is by ts
    for root in roots:
        kids = [s for s in spans
                if s.depth == 1 and root.ts <= s.ts and s.end <= root.end]
        assert [s.name for s in kids[:2]] == ["plan", "group"]
        assert any(s.name == "batch" for s in kids)
        for a, b in zip(kids, kids[1:]):
            assert a.end <= b.ts or b.end <= a.ts  # no sibling overlap
    assert all(a.ts <= b.ts for a, b in zip(spans, spans[1:]))
    # batch spans name their step family; their children are phase spans
    fams = {s.args.get("family") for s in spans if s.name == "batch"}
    assert "qt1" in fams and "qt5" in fams
    phase_names = {s.name for s in spans if s.depth == 2}
    assert phase_names <= {"pack", "compress", "compile", "dispatch",
                           "execute", "decode"}
    assert {"pack", "dispatch", "execute", "decode"} <= phase_names


def _parent(spans, s):
    """The span one level up that holds ``s`` in time on its thread."""
    return next(p for p in spans
                if p.depth == s.depth - 1 and p.tid == s.tid
                and p.ts <= s.ts and s.end <= p.end)


def _coverage(spans, root):
    """The share of ``root``'s duration that its children cover, and
    the children in order."""
    kids = [s for s in spans if s.depth == 1 and s.tid == root.tid
            and root.ts <= s.ts and s.end <= root.end]
    return sum(k.dur for k in kids) / root.dur, kids


def test_drain_children_name_the_whole_drain(served, world):
    svc, mixed, rounds = served
    spans = svc.tracer.snapshot()
    assert {"transfer", "fetch", "schedule", "respond", "stats"} <= {
        s.name for s in spans}
    for s in spans:
        if s.name == "transfer":
            assert _parent(spans, s).name in ("pack", "compress")
            assert s.args["bytes"] > 0
        elif s.name == "fetch":
            assert _parent(spans, s).name == "decode"
        elif s.name in ("schedule", "respond", "stats"):
            assert s.depth == 1 and _parent(spans, s).name == "drain"
    for root in (s for s in spans if s.name == "drain"):
        _, kids = _coverage(spans, root)
        assert [k.name for k in kids[:3]] == ["plan", "group", "schedule"]
        assert kids[-1].name == "stats"
    # a warm drain takes about 10 ms, so one preemption of a loaded host
    # between two spans can cost a few percent: judge the median drain
    idx, mesh, _ = world
    own = SearchService(idx, mesh,
                        ServeConfig(buckets=BUCKETS, max_batch=8, top_k=16))
    covered = []
    for _ in range(6):
        for q in mixed:
            own.submit(q)
        own.tracer.clear()
        own.drain()
        sp = own.tracer.snapshot()
        covered.append(_coverage(sp, next(s for s in sp if s.name == "drain"))[0])
    assert np.median(covered[1:]) >= 0.95, covered


def test_h2d_bytes_count_the_qt1_rows(typed_world):
    """A raw QT1 batch sends B*K*L*12 + B*8 bytes: three int32 rows per
    key and two float32 per query, the read count of the QT1 join."""
    idx, mesh, typed = typed_world
    cfg = ServeConfig(buckets=BUCKETS, max_batch=8, top_k=16)
    svc = SearchService(idx, mesh, cfg)
    for q in typed["qt1"]:
        svc.submit(q)
    svc.drain()
    batches = [s.args for s in svc.tracer.snapshot() if s.name == "batch"]
    assert batches and {b["family"] for b in batches} == {"qt1"}
    want = sum(b["B"] * cfg.k_fst * b["bucket"] * 12 + b["B"] * 8
               for b in batches)
    assert svc.metrics.get("serve.transfer.h2d_bytes").value == want


def _stat(v):
    """A span arg as the profiler stores it."""
    if isinstance(v, bool):
        return int(v)
    return v if isinstance(v, (int, float, str)) else str(v)


def test_spans_land_on_the_profilers_clock(world, tmp_path):
    """Under a JAX profile every span of a drain is also an event of the
    profile's host plane, with its name and args, and the two clocks
    differ by one offset."""
    import glob

    import jax
    from jax.profiler import ProfileData

    idx, mesh, mixed = world
    svc = SearchService(idx, mesh,
                        ServeConfig(buckets=BUCKETS, max_batch=8, top_k=16))
    for q in mixed:
        svc.submit(q)
    svc.drain()  # compiles outside the profile
    svc.tracer.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for q in mixed:
            svc.submit(q)
        svc.drain()
    finally:
        jax.profiler.stop_trace()
    spans = svc.tracer.snapshot()
    names = {s.name for s in spans}
    assert {"batch", "pack", "transfer", "execute", "fetch", "decode",
            "respond"} <= names
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = sorted((ev for plane in ProfileData.from_file(path).planes
                     if plane.name.startswith("/host:")
                     for line in plane.lines for ev in line.events
                     if ev.name in names), key=lambda ev: ev.start_ns)
    assert len(events) == len(spans)
    offsets = []
    for name in names:
        evs = [ev for ev in events if ev.name == name]
        sps = [s for s in spans if s.name == name]
        assert len(evs) == len(sps)
        for ev, s in zip(evs, sps):
            assert dict(ev.stats) == {k: _stat(v) for k, v in s.args.items()}
            offsets.append(ev.start_ns * 1e-9 - (svc.tracer.epoch + s.ts))
    assert max(offsets) - min(offsets) < 1e-3


# the named scopes each family's serve step carries into its ops' metadata
_I32, _F32 = np.int32, np.float32
_STEP_SCOPES = {
    "qt1": ("qt1/membership", "qt1/score", "qt1/topk"),
    "qt1_delta": ("qt1/decompress", "qt1/membership", "qt1/score", "qt1/topk"),
    "qt2": ("qt2/nearest", "qt2/score", "qt2/topk"),
    "qt34": ("qt34/nearest_r", "qt34/score", "qt34/topk"),
    "qt5": ("qt5/nearest_r", "qt5/stops", "qt5/score", "qt5/topk"),
    "qt5_pallas": ("qt5/nearest_r", "qt5/score", "qt5/topk"),
}


def _step_and_args(name, mesh, B=2, K=2, L=256):
    import jax

    from repro.core.jax_search import (make_qt1_serve_step,
                                       make_qt1_serve_step_compressed,
                                       make_wv_serve_step)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    row, vec = arg((B, K, L), _I32), arg((B,), _F32)
    if name == "qt1":
        return make_qt1_serve_step(mesh, top_k=4), (row, row, row, vec, vec)
    if name == "qt1_delta":
        off = arg((B, K, L), np.uint8)
        return (make_qt1_serve_step_compressed(mesh, top_k=4),
                (arg((B, K, L // 64), _I32), arg((B, K, L), np.uint16),
                 off, off, vec, vec))
    if name == "qt2":
        return (make_wv_serve_step(mesh, "qt2", top_k=4),
                (row, row, arg((B,), _I32), vec, vec))
    anchor, kvec = arg((B, L), _I32), arg((B, K), _I32)
    if name == "qt34":
        return (make_wv_serve_step(mesh, "qt34", top_k=4),
                (anchor, row, kvec, vec, vec))
    step = make_wv_serve_step(mesh, "qt5", top_k=4,
                              use_pallas=name == "qt5_pallas", interpret=True)
    return step, (anchor, row, kvec, row, row, kvec, vec, vec)


@pytest.mark.parametrize("name", sorted(_STEP_SCOPES))
def test_serve_steps_name_their_stages(world, name):
    _, mesh, _ = world
    step, args = _step_and_args(name, mesh)
    text = step.lower(*args).as_text(debug_info=True)
    for scope in _STEP_SCOPES[name]:
        assert scope in text, f"{name}: no {scope} scope"


# -- per-response phase breakdowns -----------------------------------------
def test_phase_breakdown_tiles_e2e_latency(served):
    svc, mixed, rounds = served
    for responses in rounds:
        assert len(responses) == len(mixed)
        for r in responses:
            assert set(r.phases) == set(PHASES)
            assert all(v >= 0.0 for v in r.phases.values())
            assert r.finished_at >= r.started_at
            # the phases tile [arrival, finished_at]: their sum agrees
            # with the end-to-end latency within the §15 bound (only the
            # per-request plan timing overlaps the queue window)
            assert sum(r.phases.values()) == pytest.approx(r.e2e_s, rel=0.10)
            assert r.deadline_blame is None  # no deadline was set
    # the same numbers aggregate into serve.phase.* histograms
    phase = svc.metrics_snapshot("serve.phase.")
    n = len(mixed) * len(rounds)
    for name in PHASES:
        assert phase[f"serve.phase.{name}"]["count"] == n


def test_deadline_miss_names_a_phase(world):
    idx, mesh, mixed = world
    svc = SearchService(idx, mesh,
                        ServeConfig(buckets=BUCKETS, max_batch=8, top_k=16))
    tickets = [svc.submit(q, deadline_s=-1.0) for q in mixed]  # unmeetable
    svc.drain()
    blamed = [t.response.deadline_blame for t in tickets]
    assert all(b in PHASES for b in blamed)
    blame = svc.stats_snapshot()["deadlines"]["miss_blame"]
    assert sum(blame.values()) == len(tickets)
    assert set(blame) == set(blamed)


# -- Chrome-trace / Perfetto export ----------------------------------------
def test_chrome_trace_export_is_valid_and_monotonic(served, tmp_path):
    svc, mixed, rounds = served
    obj = svc.trace_snapshot()
    obj = json.loads(json.dumps(obj))  # must survive a JSON round-trip
    events = obj["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    slices = [e for e in events if e["ph"] == "X"]
    assert meta and slices and {e["ph"] for e in events} == {"M", "X"}
    assert any(e["name"] == "process_name" for e in meta)
    for e in slices:
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        assert e["pid"] == 0 and isinstance(e["tid"], int)
    assert all(a["ts"] <= b["ts"] for a, b in zip(slices, slices[1:]))
    # one complete span tree per drained batch round
    assert sum(1 for e in slices if e["name"] == "drain") == len(rounds)
    # write_trace() produces the same object on disk
    path = tmp_path / "trace.json"
    written = svc.write_trace(str(path))
    assert json.loads(path.read_text()) == json.loads(json.dumps(written))


# -- est_step_cost calibration (satellite: planner feedback) ---------------
def test_est_vs_measured_calibration(served):
    svc, mixed, rounds = served
    table = svc.stats_snapshot()["plans"]["est_vs_measured"]
    assert table, "warm drains must populate the measured-cost table"
    for key, row in table.items():
        fam = key.split("/")[0]
        assert fam in ("qt1", "qt2", "qt5")
        assert row["est_step_cost"] > 0 and row["measured_p50_us"] > 0
        assert row["n"] >= 1 and row["us_per_kslot"] > 0
    # explain() stays pure and memoized; the cost view is a fresh copy
    q = mixed[0]
    p = svc.explain(q)
    assert svc.explain(q) is p
    pc = svc.explain(q, costs=True)
    assert pc is not p and pc.measured is not None
    assert pc.est_step_cost == p.est_step_cost
    assert pc.measured["est_step_cost"] == p.est_step_cost
    for entry in pc.measured["executables"].values():
        assert entry["measured_p50_us"] > 0


# -- snapshot hygiene ------------------------------------------------------
def test_stats_snapshot_is_a_deep_consistent_copy(served):
    svc, mixed, rounds = served
    snap = svc.stats_snapshot()
    assert snap["requests"] == len(mixed) * len(rounds)
    # mutating the snapshot must never touch the live stats
    snap["plans"]["routes"]["qt1"] = 10_000
    snap["bucket_hist"]["poison"] = 1
    assert svc.stats["plans"]["routes"].get("qt1") != 10_000
    assert "poison" not in svc.stats["bucket_hist"]
    json.dumps(snap)  # snapshot is plain data


def test_registry_deterministic_across_warm_drains(world):
    idx, mesh, mixed = world
    svc = SearchService(idx, mesh,
                        ServeConfig(buckets=BUCKETS, max_batch=8, top_k=16))
    # two warmup drains: the cold one compiles + fills caches, the first
    # warm one materializes the serve.step.* run-time histograms (first
    # calls are compile-timed, not run-timed)
    for _ in range(2):
        for q in mixed:
            svc.submit(q)
        svc.drain()

    def counters():
        return {n: svc.metrics.get(n).value
                for n in svc.metrics.names()
                if not hasattr(svc.metrics.get(n), "observe")
                and not n.endswith(".bytes")}

    def hist_counts():
        return {n: svc.metrics.get(n).count
                for n in svc.metrics.names()
                if hasattr(svc.metrics.get(n), "observe")}

    deltas = []
    for _ in range(2):
        c0, h0 = counters(), hist_counts()
        for q in mixed:
            svc.submit(q)
        svc.drain()
        c1, h1 = counters(), hist_counts()
        assert set(c1) == set(c0) and set(h1) == set(h0)  # no new names
        deltas.append((
            {n: c1[n] - c0[n] for n in c1},
            {n: h1[n] - h0[n] for n in h1},
        ))
    # warm drains are deterministic: identical counter increments and
    # histogram observation counts, zero compiles, all cache hits
    assert deltas[0] == deltas[1]
    cdelta, hdelta = deltas[0]
    assert all(delta == 0 for n, delta in cdelta.items() if "misses" in n)
    assert cdelta["cache.pack.hits"] > 0
    assert all(delta == 0 for n, delta in hdelta.items()
               if n.startswith("serve.compile."))
    assert hdelta["serve.phase.queue"] == len(mixed)
