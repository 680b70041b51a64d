"""Record the profiler trace that ``test_bench_trace.py`` reduces.

    python3 tests/bench/record_trace.py --out <dir>

Runs ``md9-mixed-closed`` on the chip for a 0.5 s traced window and
leaves in ``<dir>`` the trace's ``.xplane.pb`` and ``host.json`` (the
window's anchor, the service's spans and the loop's states). Gzip the
``.xplane.pb`` into ``data/trace_small_md9/window.xplane.pb.gz`` and
copy ``host.json`` beside it.
"""

import argparse
import sys

from bench_tiny import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = run.resolve_cell(run.load_benchmark(), "md9-mixed-closed", True)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    run.run_cell(cell, args.seed, 0.5, True, keep_trace=args.out,
                 use_cache=True, log=log)
    return 0


if __name__ == "__main__":
    sys.exit(main())
