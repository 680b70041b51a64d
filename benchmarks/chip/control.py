"""Readings of the correctness check: the program's and the control's.

    python3 benchmarks/chip/control.py --workload md5-frequent-poisson \\
        --seeds 11,12,13 --seconds 5

For each seed, in one process: the run's set-up and a short window of
the cell's own traffic at its own load, then the check's numbers twice
over the same sampled responses -- once for what the program answered,
once for the control: the plain reference computed at MaxDistance - 1
put in the program's place (the index a smaller MaxDistance would
build, which loses the matches at distance MaxDistance). One JSON line
per seed. The program's numbers are the check's lower readings, the
control's its upper ones; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import check
import run
from reference import Reference


def readings(cell, seed: int, seconds: float, devices, log=print) -> dict:
    config = cell.config
    c, pool, svc = run.build_service(cell, seed, devices, False, log)
    rec = run.drive(svc, pool, cell.traffic, seconds, seed)
    served = [(r.query, r.ticket.response) for r in rec.requests]
    del svc, rec
    gc.collect()
    program = check.check_run(c, config, pool, served, seed)
    low = Reference(c, config["index"]["max_distance"] - 1)
    answer = check.control_results(low, pool, config["serve"]["top_k"])
    control = check.check_run(c, config, pool, served, seed, answer=answer)
    return {"seed": seed,
            "program": {k: v["value"] for k, v in program["checks"].items()},
            "control": {k: v["value"] for k, v in control["checks"].items()},
            "program_correct": check.passed(program["checks"]),
            "control_correct": check.passed(control["checks"]),
            "first_program_mismatches": program["mismatches"][:3],
            "first_control_mismatches": control["mismatches"][:3]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        cell = run.resolve_cell(run.load_benchmark(), args.workload, False)
        devices = run.check_device(cell.entry["chips"])
        run.enable_compile_cache()
        for seed in (int(s) for s in args.seeds.split(",")):
            print(json.dumps(readings(cell, seed, args.seconds, devices, log)),
                  flush=True)
    except run.BenchFailure as e:
        log(f"control: FAILED: {e}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
