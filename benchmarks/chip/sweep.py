"""Find the knee of an open-loop cell: several offered rates in one
process, after one set-up.

    python3 benchmarks/chip/sweep.py --workload md5-frequent-poisson \\
        --seed 1 --seconds 10 --rates 200,400,600,800,1000

For each rate it replays a Poisson schedule for ``--seconds`` against
the same warmed service and prints one JSON line: the latency median
and 95th percentile from scheduled arrival to answer, the 95th
percentile over the first and the second half of the schedule, how long
the last request waited past the schedule's end, and how late the
generator ran. The knee is the highest rate whose p95 stays flat (the
second half no worse than the first) with no backlog left at the end;
the cell's traffic file takes 0.8 of it.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys

import run
from load import percentile


def sweep(cell, seed: int, seconds: float, rates, devices, log=print) -> list:
    c, pool, svc = run.build_service(cell, seed, devices, False, log)
    out = []
    for k, qps in enumerate(rates):
        traffic = copy.deepcopy(cell.traffic)
        traffic["arrivals"]["qps"] = float(qps)
        rec = run.drive(svc, pool, traffic, seconds, seed + 1000 * (k + 1))
        answered = run.stamp_answers(rec)
        lat = [a - r.due for r, a in zip(rec.requests, answered) if a is not None]
        half = rec.t0 + seconds / 2
        first = [a - r.due for r, a in zip(rec.requests, answered)
                 if a is not None and r.due < half]
        second = [a - r.due for r, a in zip(rec.requests, answered)
                  if a is not None and r.due >= half]
        late = [r.submitted - r.due for r in rec.requests]
        row = {
            "qps": qps, "requests": len(rec.requests), "answered": len(lat),
            "p50_ms": 1e3 * percentile(lat, 50), "p95_ms": 1e3 * percentile(lat, 95),
            "p95_first_half_ms": 1e3 * percentile(first, 95),
            "p95_second_half_ms": 1e3 * percentile(second, 95),
            "tail_past_schedule_ms": 1e3 * (rec.t_end - (rec.t0 + seconds)),
            "achieved_qps": len(lat) / (rec.t_end - rec.t0),
            "generator_late_p95_ms": 1e3 * percentile(late, 95),
        }
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True, help="comma-separated queries/s")
    args = ap.parse_args(argv)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        cell = run.resolve_cell(run.load_benchmark(), args.workload, False)
        if cell.traffic["arrivals"]["loop"] != "open":
            raise run.BenchFailure(f"{args.workload} is not an open-loop cell")
        devices = run.check_device(cell.entry["chips"])
        run.enable_compile_cache()
        sweep(cell, args.seed, args.seconds,
              [float(r) for r in args.rates.split(",")], devices, log)
    except run.BenchFailure as e:
        log(f"sweep: FAILED: {e}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
