"""Helpers for the benchmark's CPU tests: the harness importable, and
its cells cut to a size a test run holds."""

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
HARNESS = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(HARNESS))

import run  # noqa: E402

SEED = 2**33 + 17  # wider than 32 bits, as benchmark seeds may be


def tiny(cell_name: str, trace: bool = False, bench: dict | None = None):
    """The cell with a 300-document corpus, a short ladder and small
    batches, at a load a CPU serves."""
    bench = run.load_benchmark() if bench is None else bench
    cell = run.resolve_cell(bench, cell_name, trace)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config["corpus"]["n_docs"] = 300
    cell.config["serve"]["buckets"] = [256, 1024, 4096, 16384]
    cell.config["serve"]["max_batch"] = 8
    arr = cell.traffic["arrivals"]
    if arr["loop"] == "open":
        arr["qps"] = 100.0
    else:
        arr["callers"] = 32
    return cell


def cpu_devices(cell):
    return run.check_device(cell.entry["chips"], platform="cpu")


def quiet(_msg):
    pass
