"""Smoke run of the serving path on TPU: build a seeded index, serve
frequent-word and mixed QT1-QT5 traffic through ``SearchService``, and
check every response against the scalar ``ProximitySearchEngine``.

  python chip_smoke.py            # one chip (the default phases)
  python chip_smoke.py --chips 4  # only the doc-sharded four-chip phase

One process, JAX imported once. The run fails (non-zero exit, no JSON
line) when JAX finds no TPU, when any response differs from the
engine, when a query leaves the compiled path, or when no group runs
at L >= 16384. On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Everything printed before it is a smoke print, not a benchmark number.

Compiled executables persist in JAX's compilation cache: the directory
``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_DOCS = 20_000  # 3.2 M tokens: the smallest corpus whose long rows reach L >= 16384
N_MIXED = 64
N_STOP = 128
TOP_K = 65_536  # >= every bucket, so each response holds its full set
LONG_L = 16_384
SHARDED_BUCKETS = (1024, 4096, 16384, 65536, 262144)  # rows x doc_shards


class SmokeFailure(RuntimeError):
    pass


def check_device(n_chips: int, platform: str = "tpu"):
    """The first ``n_chips`` JAX devices; fails unless they are
    ``platform`` devices (no fallback to another backend)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise SmokeFailure(f"no {platform} device: JAX sees "
                           f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < n_chips:
        raise SmokeFailure(f"need {n_chips} {platform} devices, "
                           f"JAX sees {len(devices)}")
    print(f"device: {devices[0].device_kind} x{len(devices)} "
          f"({devices[0].platform})", flush=True)
    return devices[:n_chips]


def _n_postings(store) -> int:
    rows = store.bulk_rows()
    if rows is not None:
        return int(rows[3][0].shape[0])
    return sum(store.counts.values())


def build_phase(n_docs: int, max_distance: int = 5, seed: int = 1):
    """Seeded corpus at the serving launcher's shape, indexed with
    every additional structure; returns (table, lexicon, index)."""
    from repro.core.index_builder import build_index
    from repro.data.corpus import generate_corpus

    table, lex = generate_corpus(n_docs, mean_doc_len=160,
                                 vocab_size=40_000, seed=seed)
    t0 = time.perf_counter()
    index = build_index(table, lex, max_distance=max_distance)
    dt = time.perf_counter() - t0
    stores = {"ordinary": index.ordinary, "wv": index.wv, "fst": index.fst}
    counts = " ".join(f"{name}={_n_postings(s)}/{s.n_keys()}keys"
                      for name, s in stores.items())
    print(f"build: {n_docs} docs, {table.n_rows} tokens, MaxDistance="
          f"{max_distance}, {dt:.3f}s; postings {counts}", flush=True)
    return table, lex, index


def query_sets(table, lex, n_mixed: int, n_stop: int) -> dict:
    """The mixed QT1-QT5 set and the frequent-word set of the serving
    launcher (``launch/serve.py``)."""
    from repro.data.corpus import sample_mixed_queries, sample_stop_queries

    return {"mixed": sample_mixed_queries(table, lex, n_mixed, seed=3),
            "stop": sample_stop_queries(table, lex, n_stop, window=3, seed=2)}


def _hit_set(doc, start, end) -> set:
    return set(zip(doc.tolist(), start.tolist(), end.tolist()))


def reference_sets(index, queries: list) -> list:
    """Full (doc, start, end) result sets of the scalar engine."""
    from repro.core.search import ProximitySearchEngine

    engine = ProximitySearchEngine(index, top_k=10**9, equalize_mode="bulk")
    out = []
    for q in queries:
        res, _ = engine.search_ids(list(q))
        out.append(_hit_set(res.doc, res.start, res.end))
    return out


def serve_phase(index, mesh, sets: dict, refs: dict, config, label: str, *,
                interpret: bool = False, expected_fallbacks=frozenset()):
    """Serve every query set through one ``SearchService`` twice (cold,
    then warm) and compare each response with its reference set.
    Raises :class:`SmokeFailure` on any mismatch, on a fallback other
    than ``expected_fallbacks``, or when the service would run its
    Pallas kernel in a different mode than ``interpret``. Returns the
    phase's printed facts."""
    from repro.serving import SearchService

    svc = SearchService(index, mesh, config)
    if config.use_pallas and svc.compiled.interpret != interpret:
        raise SmokeFailure(f"{label}: Pallas interpret="
                           f"{svc.compiled.interpret}, expected {interpret}")
    drains = {}
    for run in ("cold", "warm"):
        for name, queries in sets.items():
            for q in queries:
                svc.submit(q)
            t0 = time.perf_counter()
            responses = svc.drain()
            drains[f"{run}_{name}"] = time.perf_counter() - t0
            if len(responses) != len(queries):
                raise SmokeFailure(f"{label}/{name}: {len(responses)} "
                                   f"responses for {len(queries)} queries")
            for i, (r, want) in enumerate(zip(responses, refs[name])):
                got = _hit_set(r.results["doc"], r.results["start"],
                               r.results["end"])
                if got != want:
                    raise SmokeFailure(
                        f"{label}/{name} query {i} {sets[name][i]} via "
                        f"{r.path}@L{r.bucket}: {len(got)} hits, engine "
                        f"{len(want)}; first diffs {sorted(got ^ want)[:4]}")
    stats = svc.stats_snapshot()
    fallbacks = dict(stats["plans"]["fallbacks"])
    unexpected = {k: v for k, v in fallbacks.items()
                  if k not in expected_fallbacks}
    if unexpected:
        raise SmokeFailure(f"{label}: unexpected fallbacks {unexpected}")
    n_scalar = stats["plans"]["routes"]["scalar"]
    if n_scalar != sum(fallbacks.values()):
        raise SmokeFailure(f"{label}: {n_scalar} scalar routes for "
                           f"fallbacks {fallbacks}")
    kernel_in_hlo = None
    if config.use_pallas and not interpret:
        kernel_in_hlo = any("tpu_custom_call" in fn.as_text()
                            for (kind, _b, _l), fn in svc.compiled._aot.items()
                            if kind.startswith("qt5"))
        if not kernel_in_hlo:
            raise SmokeFailure(f"{label}: no Pallas custom call in the "
                               f"qt5 executables")
    facts = {
        "label": label,
        "executables": len(svc.compiled.compile_times),
        "compile_s": sum(svc.compiled.compile_times.values()),
        "drain_s": drains,
        "bucket_hist": {b: n for b, n in stats["bucket_hist"].items() if n},
        "routes": {k: v for k, v in stats["plans"]["routes"].items() if v},
        "fallbacks": fallbacks,
        "pallas_kernel_in_hlo": kernel_in_hlo,
    }
    print(f"serve {label}: {json.dumps(facts, sort_keys=True)}", flush=True)
    return facts


def run(devices, n_docs: int, n_mixed: int, n_stop: int, *,
        interpret: bool = False, long_l: int = LONG_L):
    """Every phase of one invocation: one chip, or the doc-sharded
    phase when ``len(devices) > 1``. Returns the serve facts."""
    import dataclasses

    from repro.launch.mesh import mesh_on
    from repro.serving import ServeConfig
    from repro.serving.planner import FB_SHARDED_QT2

    table, lex, index = build_phase(n_docs)
    sets = query_sets(table, lex, n_mixed, n_stop)
    t0 = time.perf_counter()
    refs = {name: reference_sets(index, qs) for name, qs in sets.items()}
    print(f"reference: {sum(map(len, sets.values()))} queries, "
          f"{time.perf_counter() - t0:.3f}s", flush=True)
    mesh = mesh_on(devices)
    shards = len(devices)
    if shards == 1:
        base = ServeConfig(top_k=TOP_K)
        variants = [(f"{'compressed' if c else 'raw'}"
                     f"{'+pallas' if p else ''}",
                     dataclasses.replace(base, compressed=c, use_pallas=p))
                    for p in (False, True) for c in (False, True)]
        expected = frozenset()
    else:
        base = ServeConfig(top_k=TOP_K, doc_shards=shards,
                           buckets=SHARDED_BUCKETS)
        variants = [("raw", base),
                    ("compressed+pallas", dataclasses.replace(
                        base, compressed=True, use_pallas=True))]
        # the 2*MaxDistance QT2 window can cross a doc-shard cut
        expected = frozenset({FB_SHARDED_QT2})
    facts = [serve_phase(index, mesh, sets, refs, cfg,
                         f"{label}@{shards}chip", interpret=interpret,
                         expected_fallbacks=expected)
             for label, cfg in variants]
    longest = max((b for f in facts for b in f["bucket_hist"]), default=0)
    if longest < long_l:
        raise SmokeFailure(f"no group ran at L >= {long_l} "
                           f"(longest bucket {longest})")
    return facts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the doc-sharded (1, 4)-mesh phase")
    args = ap.parse_args(argv)
    try:
        devices = check_device(args.chips)
        from repro.launch.compile_cache import use_compile_cache

        cache = use_compile_cache(ROOT)
        run(devices, N_DOCS, N_MIXED, N_STOP)
        print(cache.line(), flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
