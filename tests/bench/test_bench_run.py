"""The chip benchmark's harness on the CPU: it refuses to measure without
a TPU, runs end to end at a tiny size when a test passes the platform
in, and finds configurations, traffic and metrics by name."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import HARNESS, ROOT, SEED, quiet, run, tiny

CELLS = ["md5-frequent-poisson", "md9-mixed-closed"]


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _no_result(proc):
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_run_fails_without_tpu():
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    _no_result(proc)
    assert "no tpu device" in proc.stderr


def test_benchmark_files_alone_fail(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's own
    directories has no program to run: non-zero exit, no result."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        bench["command"] + ["--workload", CELLS[1], "--seed", str(SEED),
                            "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True, timeout=300)
    _no_result(proc)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell_name", CELLS)
def test_tiny_cell_end_to_end(cell_name, trace):
    cell = tiny(cell_name, bool(trace))
    res = run.run_cell(cell, SEED, 1.0, bool(trace), platform="cpu", log=quiet)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["checked"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    got = set(res["metrics"])
    want = {m["name"] for m in cell.metrics}
    if trace:
        # device metrics come only from a TPU's trace, never from the CPU
        device = {m["name"] for m in cell.metrics if m["source"] == "device_trace"}
        assert got == want - device and device
        assert "busy_s" not in res["device"]
    else:
        assert got == want and "setup_s" in got
    for m in res["metrics"].values():
        assert m["value"] > 0


def test_cells_report_setup_and_their_layers():
    bench = run.load_benchmark()
    assert {w["name"] for w in run.load_benchmark()["workloads"]} <= set(CELLS)
    for name in CELLS:
        e2e = run.resolve_cell(bench, name, False).metrics
        layer = run.resolve_cell(bench, name, True).metrics
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert layer and all(m["moves"] in {e["name"] for e in e2e} for m in layer)
        for m in e2e + layer:
            assert (HARNESS / "metrics" / f"{m['name']}.py").is_file()


def test_found_by_name(tmp_path):
    """A new configuration, traffic mix and metric are files and entries
    only: the harness finds each by the name BENCHMARK.json gives."""
    root = tmp_path
    here = root / "benchmarks" / "chip"
    shutil.copytree(HARNESS, here, ignore=shutil.ignore_patterns("__pycache__"))
    bench = run.load_benchmark()
    cfg = json.loads((here / "configs" / "fiction-md5.json").read_text())
    cfg["name"] = "fiction-md7"
    cfg["index"]["max_distance"] = 7
    (here / "configs" / "fiction-md7.json").write_text(json.dumps(cfg))
    traffic = json.loads((here / "traffic" / "frequent-poisson.json").read_text())
    traffic["arrivals"]["process"] = "bursty"
    (here / "traffic" / "frequent-bursty.json").write_text(json.dumps(traffic))
    (here / "metrics" / "answered_share.py").write_text(
        "def read(run):\n    return 100.0 * run.answered / run.attempted\n")
    bench["configs"].append({"name": "fiction-md7", "source": "x",
                             "file": "benchmarks/chip/configs/fiction-md7.json",
                             "reduced": ["n_docs"], "why": "x"})
    bench["workloads"].append({"name": "md7-frequent-bursty", "config": "fiction-md7",
                               "traffic": "frequent-bursty", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "answered_share", "unit": "%",
                               "better": "higher", "source": "program_counter",
                               "layer": "service queue", "moves": "latency_p95_ms",
                               "workloads": ["md7-frequent-bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    bench = run.load_benchmark(root)
    cell = run.resolve_cell(bench, "md7-frequent-bursty", True, root=root, here=here)
    assert cell.config["index"]["max_distance"] == 7
    assert cell.traffic["arrivals"]["process"] == "bursty"
    assert [m["name"] for m in cell.metrics] == ["answered_share"]
    cell.config["corpus"]["n_docs"] = 300
    cell.config["serve"]["buckets"] = [256, 1024, 4096, 16384]
    cell.config["serve"]["max_batch"] = 8
    cell.traffic["arrivals"]["qps"] = 100.0
    res = run.run_cell(cell, SEED, 1.0, True, platform="cpu", log=quiet)
    assert res["correct"] is True
    assert res["metrics"]["answered_share"] == {"value": 100.0, "unit": "%"}
    with pytest.raises(run.BenchFailure, match="no workload"):
        run.resolve_cell(bench, "no-such-cell", False, root=root, here=here)


def test_seed_changes_order_not_sizes():
    """Two seeds serve the same documents, pool and arrival gaps, each in
    its own order; one seed always gives the same inputs."""
    import numpy as np

    import load  # noqa: E402  (the harness directory is on the path)

    cell = tiny(CELLS[0])
    a, pool_a = run.generate(cell.config, cell.traffic, SEED)
    b, pool_b = run.generate(cell.config, cell.traffic, SEED + 1)
    a2, _ = run.generate(cell.config, cell.traffic, SEED)
    assert pool_a == pool_b
    assert np.array_equal(a.lemma_ids, a2.lemma_ids)
    assert not np.array_equal(a.doc_lengths, b.doc_lengths)
    assert sorted(a.doc_lengths.tolist()) == sorted(b.doc_lengths.tolist())
    assert np.array_equal(np.bincount(a.lemma_ids), np.bincount(b.lemma_ids))
    arr = cell.traffic["arrivals"]
    base = load.poisson_arrivals(arr["qps"], 5.0, arr["seed"])
    ra = load.rotate(base, 5.0, np.random.default_rng(SEED + 2))
    rb = load.rotate(base, 5.0, np.random.default_rng(SEED + 3))
    assert len(ra) == len(rb) == len(base) and ra != rb
    gaps = lambda xs: sorted(np.round(np.diff(xs), 9).tolist())  # noqa: E731
    assert len(set(gaps(ra)) & set(gaps(base))) >= len(base) - 3
    cyc = load.shuffled_cycles(len(pool_a), np.random.default_rng(SEED))
    first = [next(cyc) for _ in range(len(pool_a))]
    assert sorted(first) == list(range(len(pool_a)))
