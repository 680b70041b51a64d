"""Planner-layer coverage (DESIGN.md §14): for every row of the §13
dispatch matrix — each query type on its compiled route and each
scalar-fallback shape — ``explain()`` must return the expected
route/payload/``fallback_reason``, and the executed ``response.plan``
must agree with the pre-computed plan. Plus the dispatch-aware
batching acceptance: ``CompiledExecutor`` demonstrably shares B-bucket
executables across the qt34 and qt5 paths (via engine stats)."""

import dataclasses

import pytest

from repro.core.index_builder import build_index
from repro.core.lexicon import UNKNOWN_FL
from repro.core.query import QueryType, classify, qt34_plan
from repro.core.search import ProximitySearchEngine
from repro.data.corpus import generate_corpus, sample_typed_queries
from repro.launch.mesh import make_mesh
from repro.serving import QueryPlan, SearchService, ServeConfig
from repro.serving import planner

D = 5
BUCKETS = (256, 1024)


@pytest.fixture(scope="module")
def world():
    table, lex = generate_corpus(n_docs=80, mean_doc_len=70, vocab_size=500, seed=11)
    lex.sw_count = 14
    lex.fu_count = 30
    idx = build_index(table, lex, max_distance=D)
    mesh = make_mesh((1, 1), ("data", "model"))
    queries = {
        k: sample_typed_queries(table, lex, 10, k, window=D, seed=3)
        for k in ("qt1", "qt2", "qt3", "qt4", "qt5")
    }
    return table, lex, idx, mesh, queries


def _service(idx, mesh, **over):
    over = {"buckets": BUCKETS, "max_batch": 8, "top_k": 256, **over}
    return SearchService(idx, mesh, ServeConfig(**over))


def _cpu_set(idx, q):
    res, _ = ProximitySearchEngine(idx, top_k=100_000,
                                   equalize_mode="bulk").search_ids(q)
    return set(zip(res.doc.tolist(), res.start.tolist(), res.end.tolist()))


def _resp_set(r):
    return set(zip(r.results["doc"].tolist(), r.results["start"].tolist(),
                   r.results["end"].tolist()))


# -- compiled rows of the matrix: QT1-QT5 x route x payload ----------------
@pytest.mark.parametrize("kind,route,family,qtype", [
    ("qt1", "qt1", "qt1", QueryType.QT1),
    ("qt2", "qt2", "qt2", QueryType.QT2),
    ("qt3", "qt34", "qt5", QueryType.QT3),   # share_buckets default: on
    ("qt4", "qt34", "qt5", QueryType.QT4),
    ("qt5", "qt5", "qt5", QueryType.QT5),
])
@pytest.mark.parametrize("compressed", [False, True])
def test_compiled_matrix_rows(world, kind, route, family, qtype, compressed):
    table, lex, idx, mesh, queries = world
    svc = _service(idx, mesh, compressed=compressed)
    qs = [q for q in queries[kind]
          if svc.explain(q).route == route][:6]
    assert qs, f"no {kind} queries plan onto route {route}"
    for q in qs:
        p = svc.explain(q)
        assert p.qtype == qtype
        assert p.route == route
        assert p.step_family in (family, route)
        assert p.bucket in BUCKETS
        assert p.fallback_reason is None
        assert p.is_compiled
        # predicted payload: raw uncompressed; delta16 when the bucket
        # is block-aligned (both BUCKETS are)
        assert p.payload == ("delta16" if compressed else "raw")
        assert p.est_step_cost is not None and p.est_step_cost > 0
    tickets = [svc.submit(q) for q in qs]
    responses = svc.drain()
    for q, t, r in zip(qs, tickets, responses):
        assert t.response is r
        pre = svc.explain(q)
        # the executed plan agrees with the pre-computed one (payload
        # may downgrade delta16 -> offsets on uint16 overflow; not on
        # this corpus)
        assert r.plan.route == pre.route == r.path
        assert r.plan.step_family == pre.step_family
        assert r.plan.bucket == pre.bucket == r.bucket
        assert r.plan.payload == pre.payload
        assert _resp_set(r) == _cpu_set(idx, q)


# -- scalar-fallback rows of the matrix ------------------------------------
def _fallback_cases(idx, lex, queries):
    """(case name, query, expected qtype, expected reason, config
    overrides) — one entry per CPU-fallback condition of the DESIGN.md
    §13 matrix that is reachable through ``classify``."""
    from repro.core.query import qt1_plan, qt2_plan

    sw, fu = lex.sw_count, lex.fu_count
    stop0 = int(queries["qt1"][0][0])
    ord0 = int(queries["qt3"][0][0])
    # ladder overflow needs a posting row longer than the tiny bucket
    q1_long = next(q for q in queries["qt1"] if qt1_plan(idx, q)[1] > 16)
    # (w,v) keys are sparse on this corpus: a 2-slot ladder overflows
    q2_long = next(q for q in queries["qt2"] if qt2_plan(idx, q)[1] > 2)
    q4_long = next(q for q in queries["qt4"]
                   if max(qt34_plan(idx, q)[2].values()) > 16)
    return [
        ("unknown_lemma", [stop0, UNKNOWN_FL], None,
         planner.FB_UNKNOWN_LEMMA, {}),
        ("qt1_short", [stop0, stop0 + 1], QueryType.QT1,
         planner.FB_QUERY_TOO_SHORT, {}),
        ("qt1_long", [0, 1, 2, 3, 4, 5, 0], QueryType.QT1,
         planner.FB_QUERY_TOO_LONG, {}),
        ("qt1_keys", queries["qt1"][0], QueryType.QT1,
         planner.FB_TOO_MANY_FST_KEYS, {"k_fst": 0}),
        ("qt1_ladder", q1_long, QueryType.QT1,
         planner.FB_ROW_EXCEEDS_LADDER, {"buckets": (16,)}),
        ("qt2_sharded", queries["qt2"][0], QueryType.QT2,
         planner.FB_SHARDED_QT2, {"doc_shards": 2}),
        ("qt2_keys", list(range(sw, sw + 8)), QueryType.QT2,
         planner.FB_TOO_MANY_WV_KEYS, {}),
        ("qt2_ladder", q2_long, QueryType.QT2,
         planner.FB_ROW_EXCEEDS_LADDER, {"buckets": (2,)}),
        ("qt34_constraints", [int(l) for l in range(sw + fu, sw + fu + 6)],
         QueryType.QT3, planner.FB_TOO_MANY_ORD_CONSTRAINTS, {}),
        ("qt34_rmax", [ord0] * 6, QueryType.QT3,
         planner.FB_MULTIPLICITY_OVER_R_MAX, {}),
        ("qt34_ladder", q4_long, QueryType.QT4,
         planner.FB_ROW_EXCEEDS_LADDER, {"buckets": (16,)}),
        # 5 non-stop lemmas: the rarest anchors, leaving 4 others > k_ns
        ("qt5_ns_constraints", [stop0] + [int(l) for l in
                                          range(sw + fu, sw + fu + 5)],
         QueryType.QT5, planner.FB_TOO_MANY_NS_CONSTRAINTS, {}),
        ("qt5_stop_constraints", [0, 1, 2, 3, ord0], QueryType.QT5,
         planner.FB_TOO_MANY_STOP_CONSTRAINTS, {}),
        ("qt5_rmax", [stop0] + [ord0] * 5, QueryType.QT5,
         planner.FB_MULTIPLICITY_OVER_R_MAX, {}),
        ("qt5_stop_overflow", [stop0] * 255 + [ord0], QueryType.QT5,
         planner.FB_STOP_MULTIPLICITY_OVERFLOW, {}),
        # a query lemma lives in the unsealed-memtable overlay (§18):
        # compiled caches would churn per add, so the row goes scalar
        ("live_memtable", queries["qt1"][0], QueryType.QT1,
         planner.FB_LIVE_MEMTABLE, {"_live_overlay": True}),
    ]


def _live_seg(table, lex, q):
    """A segmented index whose sealed tier is the module corpus and whose
    unsealed memtable holds one extra doc containing the query lemmas."""
    from repro.index import SegmentedIndex

    seg = SegmentedIndex(lex, max_distance=D, memtable_docs=1000)
    for d in table.to_doc_lists():
        seg.add_document(d)
    seg.refresh()
    seg.add_document(list(q) * 2)  # stays in the memtable: overlay-only
    return seg


def test_scalar_fallback_rows(world):
    table, lex, idx, mesh, queries = world
    for name, q, qtype, reason, over in _fallback_cases(idx, lex, queries):
        ref = idx
        if over.pop("_live_overlay", False):
            seg = _live_seg(table, lex, q)
            svc = _service(seg, mesh, serve_memtable=True, **over)
            svc.refresh()  # pulls live_view(): overlay becomes visible
            ref = seg.live_view()
        elif over.get("doc_shards", 1) > 1:
            svc = None  # a 1-device mesh cannot hold 2 shards: plan only
        else:
            svc = _service(idx, mesh, **over)
        if svc is None:
            cfg = ServeConfig(**{"buckets": BUCKETS, "max_batch": 8,
                                 "top_k": 256, **over})
            p = planner.plan(q, idx, cfg)
        else:
            p = svc.explain(q)
        assert p.route == planner.ROUTE_SCALAR, (name, p)
        assert p.qtype == qtype, name
        assert p.fallback_reason == reason, (name, p.fallback_reason)
        assert p.bucket is None and p.payload is None
        assert p.est_step_cost is None  # no compiled-shape bound — the point
        if svc is None:
            continue
        t = svc.submit(q)
        (r,) = svc.drain()
        assert r.path == "cpu" and r.plan == p, name
        assert t.response is r
        assert _resp_set(r) == _cpu_set(ref, q), name
    # empty requests are their own (inline) dispatch row
    svc = _service(idx, mesh)
    assert svc.explain([]) == QueryPlan(qtype=None, route=planner.ROUTE_EMPTY)
    svc.submit([])
    (r,) = svc.drain()
    assert r.path == "empty" and r.results["doc"].size == 0


def test_doc_shards_must_match_mesh(world):
    """Each device of the mesh's model axis holds one doc-range shard:
    a config that splits rows differently is refused at construction
    instead of silently losing matches that straddle a shard cut."""
    table, lex, idx, mesh, queries = world
    with pytest.raises(ValueError, match="doc_shards"):
        _service(idx, mesh, doc_shards=2)


def test_every_matrix_reason_is_covered(world):
    """The fallback-case table above must cover every reachable reason
    constant the planner can emit — a new matrix row without a test row
    fails here."""
    table, lex, idx, mesh, queries = world
    covered = {reason for _, _, _, reason, _ in _fallback_cases(idx, lex, queries)}
    all_reasons = {v for k, v in vars(planner).items() if k.startswith("FB_")}
    # no-store reasons need an index built without the structure;
    # degenerate QT5 is unreachable through classify (defensive)
    reachable = all_reasons - {
        planner.FB_NO_FST_INDEX, planner.FB_NO_WV_INDEX,
        planner.FB_NO_ORDINARY_INDEX, planner.FB_NO_NSW_INDEX,
        planner.FB_DEGENERATE_QT5,
    }
    assert covered == reachable, covered ^ reachable


def test_missing_store_fallbacks(world):
    """Idx1-style indexes (additional structures disabled) route every
    affected type to the scalar engine with the matching reason."""
    table, lex, idx, mesh, queries = world
    cfg = ServeConfig(buckets=BUCKETS)
    for field, q, reason in [
        ("fst", queries["qt1"][0], planner.FB_NO_FST_INDEX),
        ("wv", queries["qt2"][0], planner.FB_NO_WV_INDEX),
        ("nsw", queries["qt5"][0], planner.FB_NO_NSW_INDEX),
        # the ordinary guard protects qt34_plan/qt5_plan, which would
        # otherwise dereference index.ordinary.n_postings and crash
        ("ordinary", queries["qt3"][0], planner.FB_NO_ORDINARY_INDEX),
        ("ordinary", queries["qt5"][0], planner.FB_NO_ORDINARY_INDEX),
    ]:
        bare = dataclasses.replace(idx, **{field: None})
        p = planner.plan(q, bare, cfg)
        assert p.route == planner.ROUTE_SCALAR
        assert p.fallback_reason == reason, field


def test_plan_is_pure_and_memoized(world):
    table, lex, idx, mesh, queries = world
    cfg = ServeConfig(buckets=BUCKETS)
    q = queries["qt3"][0]
    assert planner.plan(q, idx, cfg) == planner.plan(list(q), idx, cfg)
    svc = _service(idx, mesh)
    assert svc.explain(q) is svc.explain(q)  # memoized per snapshot


# -- dispatch-aware batching (the acceptance criterion) --------------------
def test_qt34_shares_qt5_executables(world):
    """With share_buckets (default), qt34 groups whose plans fit the
    QT5 step's non-stop slots ride the qt5 executable of the same
    (B, L): the executable table gains no qt34 kind at all, the stats
    count shared batches — and results still match the CPU reference
    bit-for-bit (qt5_join with zero stop constraints is qt34_join)."""
    table, lex, idx, mesh, queries = world
    qs = [q for q in queries["qt3"] + queries["qt4"] + queries["qt5"]
          if len(qt34_plan(idx, q)[1]) <= 3 or classify(q, lex) == QueryType.QT5]
    shared = _service(idx, mesh)
    solo = _service(idx, mesh, share_buckets=False)
    for q in qs:
        shared.submit(q)
        solo.submit(q)
    got_shared = [_resp_set(r) for r in shared.drain()]
    got_solo = [_resp_set(r) for r in solo.drain()]
    assert got_shared == got_solo == [_cpu_set(idx, q) for q in qs]
    # shared engine: qt34 traffic executed, yet only qt5 executables exist
    assert shared.stats["paths"]["qt34"] > 0 and shared.stats["paths"]["qt5"] > 0
    kinds_shared = {k for (k, B, L) in shared.compiled.executables}
    assert any(k.startswith("qt5_") for k in kinds_shared)
    assert not any(k.startswith("qt34_") for k in kinds_shared)
    assert shared.stats["plans"]["shared_batches"] > 0
    # control: without sharing the qt34 path compiles its own executables
    kinds_solo = {k for (k, B, L) in solo.compiled.executables}
    assert any(k.startswith("qt34_") for k in kinds_solo)
    assert solo.stats["plans"]["shared_batches"] == 0
    assert shared.compiled.n_executables < solo.compiled.n_executables


def test_qt34_and_qt5_batch_together(world):
    """Sharing is batching, not just executable reuse: qt34 and qt5
    requests at the same (B, L) land in one padded batch."""
    table, lex, idx, mesh, queries = world
    svc = _service(idx, mesh)
    qs = [q for q in queries["qt3"][:4] + queries["qt5"][:4]
          if svc.explain(q).step_family == "qt5"
          and svc.explain(q).bucket == BUCKETS[0]]
    assert len({svc.explain(q).route for q in qs}) == 2, "need both routes"
    for q in qs:
        svc.submit(q)
    responses = svc.drain()
    assert svc.stats["batches"] == 1  # one fused batch served everything
    assert {r.path for r in responses} == {"qt34", "qt5"}
    for q, r in zip(qs, responses):
        assert _resp_set(r) == _cpu_set(idx, q)


def test_deadline_and_queue_wait_reporting(world):
    table, lex, idx, mesh, queries = world
    svc = _service(idx, mesh)
    generous = svc.submit(queries["qt1"][0], deadline_s=60.0)
    hopeless = svc.submit(queries["qt1"][1], deadline_s=-1.0)
    unset = svc.submit(queries["qt1"][2])
    svc.drain()
    assert generous.response.deadline_met is True
    assert hopeless.response.deadline_met is False
    assert unset.response.deadline_met is None
    assert all(t.response.queue_wait_s >= 0.0
               for t in (generous, hopeless, unset))
    assert svc.stats["deadlines"] == {
        "met": 1, "missed": 1, "unset": 1,
        # §15 phase attribution: a -1s budget is blown before the batch
        # even starts, so the miss is blamed on the queue
        "miss_blame": {"queue": 1},
    }
