"""Helpers for the benchmark's CPU tests: the harness importable, its
cells cut to a size a test run holds, and a tiny run of any cell on as
many host CPU devices as the cell asks for."""

import copy
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
HARNESS = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(HARNESS))

import run  # noqa: E402

SEED = 2**33 + 17  # wider than 32 bits, as benchmark seeds may be
WINDOW_S = 1.0  # the tiny runs' window


def cell_names(root: Path = ROOT) -> list:
    """Every cell of ``<root>/BENCHMARK.json``, in its order."""
    return [w["name"] for w in run.load_benchmark(root)["workloads"]]


def first_cell_of_loop(loop: str, root: Path = ROOT) -> str:
    """The first cell of BENCHMARK.json whose traffic is an ``loop``
    ("open" or "closed") loop."""
    bench = run.load_benchmark(root)
    for name in cell_names(root):
        if run.resolve_cell(bench, name, False, root=root).traffic["arrivals"]["loop"] == loop:
            return name
    raise LookupError(f"no {loop}-loop cell in BENCHMARK.json")


def tiny(cell_name: str, trace: bool = False, bench: dict | None = None,
         root: Path = ROOT):
    """The cell with a 300-document corpus, a short ladder and small
    batches, at a load a CPU serves."""
    bench = run.load_benchmark(root) if bench is None else bench
    cell = run.resolve_cell(bench, cell_name, trace, root=root)
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


def _plant_answer_altered(patch):
    from repro.serving import executors

    real = executors.decode_results

    def decode(batch, *outs):
        out = real(batch, *outs)
        for res in out:
            if res["doc"].size:
                res["end"] = res["end"] + 1
        return out

    patch(executors, "decode_results", decode)


def _plant_half_batch_left_out(patch):
    from repro.serving import executors

    real = executors.CompiledExecutor.execute

    def execute(self, index, queries, selections, **kw):
        out = real(self, index, queries, selections, **kw)
        for ex in out[len(out) // 2:]:
            ex.results = {k: v[:0] for k, v in ex.results.items()}
        return out

    patch(executors.CompiledExecutor, "execute", execute)


# faults planted in the timed path, under which a run must not be correct:
# an answer altered where it is produced, half of each batch's answers lost
FAULTS = {"answer_altered": _plant_answer_altered,
          "half_batch_left_out": _plant_half_batch_left_out}


def tiny_here(cell, trace: bool, mode: str = "run", fault: str | None = None,
              patch=setattr) -> dict:
    """A tiny run of ``cell`` (from :func:`tiny`) in this process, on as
    many of its host CPU devices as the cell asks for: its result
    (``mode`` "run") or the control's readings (``mode`` "control"),
    with ``fault`` of :data:`FAULTS` planted through ``patch`` (a
    ``setattr``, or a test's ``monkeypatch.setattr``)."""
    if fault is not None:
        FAULTS[fault](patch)
    if mode == "control":
        import control

        return control.readings(cell, SEED, WINDOW_S, cpu_devices(cell), log=quiet)
    return run.run_cell(cell, SEED, WINDOW_S, trace, platform="cpu", log=quiet)


def tiny_outcome(cell, trace: bool, mode: str = "run", root: Path = ROOT,
                 fault: str | None = None, patch=setattr) -> dict:
    """:func:`tiny_here`, for a cell of any number of chips. A one-chip
    cell runs in this process; a cell of more chips in a subprocess
    (``tiny_cell.py``) with that many virtual devices, since a process's
    device count is fixed when JAX starts."""
    chips = cell.entry["chips"]
    if chips == 1:
        return tiny_here(cell, trace, mode, fault, patch)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    cmd = [sys.executable, str(Path(__file__).parent / "tiny_cell.py"),
           "--workload", cell.name, "--trace", str(int(trace)), "--mode", mode,
           "--root", str(root)]
    if fault is not None:
        cmd += ["--fault", fault]
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-8000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
