"""One tiny run of a benchmark cell on host CPU devices, for the tests.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 tests/bench/tiny_cell.py --workload <cell> --trace 0 \\
        [--mode run|control] [--root <checkout>] [--fault <name>]

Cuts the cell of ``<root>/BENCHMARK.json`` to the tests' size
(``bench_tiny.tiny``) and prints, as the last line of stdout, the run's
result (``--mode run``) or the control's readings (``--mode control``),
with one of ``bench_tiny.FAULTS`` planted in the timed path if ``--fault``
names it.
``bench_tiny.tiny_outcome`` runs a cell of more than one chip this way,
with as many virtual devices as the cell asks for.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # never probe for an accelerator

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

from bench_tiny import FAULTS, ROOT, tiny, tiny_here  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--mode", choices=("run", "control"), default="run")
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    root = Path(args.root)
    cell = tiny(args.workload, bool(args.trace), root=root)
    out = tiny_here(cell, bool(args.trace), args.mode, args.fault)
    print(json.dumps(out, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
