"""The comparison that decides ``correct``: the plain reference agrees
with the program on sound runs, and the check comes out false for the
control and for a timed path broken underneath."""

import numpy as np
import pytest

from bench_tiny import SEED, cpu_devices, quiet, run, tiny

import check  # noqa: E402  (the harness directory is on the path)
import control  # noqa: E402
import corpus as bench_corpus  # noqa: E402
from reference import Reference, check_response  # noqa: E402


@pytest.mark.parametrize("n_docs,d,seed,small_classes", [
    (300, 5, 1, False), (300, 9, 2, False), (400, 9, 4, True), (400, 5, 5, True)])
def test_reference_matches_program(n_docs, d, seed, small_classes):
    """Every lane set equals the scalar engine's full result set, and every
    top-16 response of the compiled and scalar routes passes the check.
    Small SWCount/FUCount crowd the windows with repeated lemmas."""
    import jax

    from repro.core.index_builder import build_index
    from repro.core.search import ProximitySearchEngine
    from repro.launch.mesh import mesh_on
    from repro.serving import SearchService, ServeConfig

    c = bench_corpus.generate_corpus(n_docs, 160, 40000, 1.1, 700, 2100, seed)
    if small_classes:
        c.sw_count, c.fu_count = 25, 60
    table, lex = run.to_program(c)
    index = build_index(table, lex, max_distance=d)
    ref = Reference(c, d)
    queries = (bench_corpus.sample_stop_queries(c, 40, window=3, seed=seed + 1)
               + bench_corpus.sample_mixed_queries(c, 100, window=9, seed=seed + 2))
    engine = ProximitySearchEngine(index, top_k=10**9, equalize_mode="bulk")
    for q in queries:
        res, _ = engine.search_ids(q)
        lanes = ref.lanes(q)
        assert (set(zip(res.doc.tolist(), res.start.tolist(), res.end.tolist()))
                == set(zip(lanes.doc.tolist(), lanes.start.tolist(),
                           lanes.end.tolist()))), q
    svc = SearchService(index, mesh_on(jax.devices()[:1]),
                        ServeConfig(buckets=(256, 1024, 4096, 16384), max_batch=8))
    tickets = [svc.submit(q) for q in queries]
    svc.drain()
    paths = set()
    for q, t in zip(queries, tickets):
        r = t.result()
        paths.add(r.path)
        assert check_response(ref.lanes(q), r.results, 16, r.path == "cpu") is None
    assert {"qt1", "qt2", "qt34", "qt5"} <= paths


def test_samplers_match_program():
    from repro.data import corpus as program

    c = bench_corpus.generate_corpus(500, 160, 40000, 1.1, 700, 2100, SEED)
    table, lex = program.generate_corpus(500, mean_doc_len=160, vocab_size=40000,
                                         seed=SEED)
    assert np.array_equal(table.lemma_ids, c.lemma_ids)
    assert np.array_equal(lex.doc_freqs, c.doc_freqs)
    assert (program.sample_stop_queries(table, lex, 80, window=3, seed=SEED + 1)
            == bench_corpus.sample_stop_queries(c, 80, window=3, seed=SEED + 1))
    assert (program.sample_mixed_queries(table, lex, 80, window=9, seed=SEED + 2)
            == bench_corpus.sample_mixed_queries(c, 80, window=9, seed=SEED + 2))


@pytest.mark.parametrize("cell_name", ["md5-frequent-poisson", "md9-mixed-closed"])
def test_control_is_not_correct(cell_name):
    """The reference at MaxDistance - 1 in the program's place fails the
    check that the program passes on the same responses."""
    cell = tiny(cell_name)
    r = control.readings(cell, SEED, 1.0, cpu_devices(cell), log=quiet)
    assert r["program_correct"] and r["program"]["mismatched"] == 0
    assert not r["control_correct"] and r["control"]["mismatched"] > 0


def _altered_decode(real):
    def decode(batch, *outs):
        out = real(batch, *outs)
        for res in out:
            if res["doc"].size:
                res["end"] = res["end"] + 1
        return out
    return decode


def _half_batch(real):
    def execute(self, index, queries, selections, **kw):
        out = real(self, index, queries, selections, **kw)
        for ex in out[len(out) // 2:]:
            ex.results = {k: v[:0] for k, v in ex.results.items()}
        return out
    return execute


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch_left_out"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.serving import executors

    if fault == "answer_altered":
        monkeypatch.setattr(executors, "decode_results",
                            _altered_decode(executors.decode_results))
    else:
        monkeypatch.setattr(executors.CompiledExecutor, "execute",
                            _half_batch(executors.CompiledExecutor.execute))
    cell = tiny("md9-mixed-closed")
    res = run.run_cell(cell, SEED, 1.0, False, platform="cpu", log=quiet)
    assert res["correct"] is False
    assert res["checks"]["mismatched"]["value"] > 0


def test_unanswered_requests_are_not_correct():
    checks, _ = check.compare(None, [[1, 2, 3]], [(0, None)], [], 16)
    assert checks["unanswered"]["value"] == 1 and not check.passed(checks)
