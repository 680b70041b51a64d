"""Benchmark harness — one section per paper table/figure plus kernel and
serving micro-benches. Prints ``name,us_per_call,derived`` CSV.

Sections:
  search/*    — the paper's Idx1 vs Idx2/3/4 experiment (Figs. 6-9);
  equalize/*  — §2.3 heap vs basic Equalize scaling;
  kernel/*    — posting-intersection / proximity / embedding-bag ops;
  serve/*     — compiled QT1 serve-step latency per bucket, packed-posting
                cache cold/warm packing, engine drains uncached/cached/
                compressed, and closed-loop deadline met-rates;
  load        — open-loop load (rows under serve/): controlled
                (admission on, §17) vs uncontrolled deadline met-rates
                at sustained/overload/bursty offered rates
                (benchmarks/load_bench.py);
  churn/*     — segmented-index throughput + latency under add/delete/
                merge churn (repro.index) with background compaction and
                live-memtable serving (§18), incl. serve-cache hit rate,
                refresh p95, and ingest docs/sec;
  tune/*      — §19 parameter autotuner: successive-halving sweep of
                the joint (MaxDistance, ServeConfig) space on the mixed
                workload, winner cross-evaluated vs the default config
                on zipfian/longtail/stopflood/mixed traffic and emitted
                to results/tuned_serve_config.json
                (benchmarks/tune_bench.py).

Quick mode (default) uses a reduced corpus; --full matches the corpus
scale used in EXPERIMENTS.md; --smoke is the tiny-corpus CI invocation.
``--json [PATH]`` writes the serve + churn reports (cache hit rates,
cold/warm drain latencies) to PATH (default BENCH_serve.json) so the
perf trajectory is tracked across PRs.

Compiled executables persist in JAX's compilation cache: the directory
``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
from pathlib import Path

from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="EXPERIMENTS.md-scale corpus")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpus, few reps (CI smoke)")
    ap.add_argument("--only", default=None, help="comma-separated section filter")
    ap.add_argument("--json", nargs="?", const="BENCH_serve.json", default=None,
                    metavar="PATH",
                    help="write serve+churn reports as JSON (default %(const)s)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    cache = use_compile_cache(Path(__file__).resolve().parents[1])

    rows: list[tuple] = []
    reports: dict = {}

    def want(section: str) -> bool:
        return only is None or section in only

    if want("search"):
        from benchmarks import paper_experiments

        if args.full:
            rep = paper_experiments.run()
        else:
            rep = paper_experiments.run(n_docs=1200, mean_doc_len=140, n_queries=150,
                                        out_json="results/paper_experiments_quick.json")
        rows += paper_experiments.rows(rep)

    if want("equalize"):
        from benchmarks import equalize_scaling

        rows += equalize_scaling.run()

    if want("kernel"):
        from benchmarks import kernel_bench

        rows += kernel_bench.run(smoke=args.smoke)

    if want("serve"):
        from benchmarks import serve_bench

        serve_rows, serve_rep = serve_bench.run(smoke=args.smoke)
        rows += serve_rows
        reports["serve"] = serve_rep

    if want("load"):
        from benchmarks import load_bench

        load_rows, load_rep = load_bench.run(smoke=args.smoke)
        rows += load_rows
        reports["load"] = load_rep

    if want("churn"):
        from benchmarks import churn_bench

        # background + live-memtable serving is the §18 default: refresh
        # seals and schedules, merges run on the CompactionExecutor
        if args.full:
            rep = churn_bench.run(serve=True, background=True, serve_memtable=True)
        elif args.smoke:
            rep = churn_bench.run(n_docs=150, chunk=40, memtable_docs=24, serve=True,
                                  background=True, serve_memtable=True)
        else:
            rep = churn_bench.run(n_docs=400, chunk=40, serve=True,
                                  background=True, serve_memtable=True)
        rows += churn_bench.rows(rep)
        reports["churn"] = rep

    if want("tune"):
        from benchmarks import tune_bench

        tune_rows, tune_rep = tune_bench.run(smoke=args.smoke)
        rows += tune_rows
        reports["tune"] = tune_rep

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    print(cache.line(), file=sys.stderr)

    if args.json:
        payload = {
            "python": platform.python_version(),
            "mode": "full" if args.full else ("smoke" if args.smoke else "quick"),
            "rows": [
                {"name": n, "us_per_call": us, "derived": d} for n, us, d in rows
            ],
            "reports": reports,
        }
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
