"""Run one benchmark cell once on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it fails when JAX finds no TPU (or fewer chips than the
cell asks for), generates the cell's corpus and query pool from the
seed, builds the index with the program's ``build_index``, makes a
``SearchService`` with the configuration's serving settings, warms
every shape the pool routes to (from the compile cache at
``<checkout>/.jax_cache``), serves the pool once so that the row cache
holds what a server of this pool holds, drives the cell's traffic through
``submit()``/``drain()`` for ``--seconds``, and checks the answers of a
seeded sample of the window's queries against the plain reference.

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); every metric is a reader
``metrics/<name>.py``, and every kernel's work count a file
``kernels/<family>.py``. ``--trace 0`` prints the cell's end-to-end
metrics; ``--trace 1`` runs the JAX profiler over the window, turns on
the service's span tracer, and prints the per-layer metrics, the
device's busy time and a breakdown.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and last ``checks``: each number the correctness check
compared, with its limit. The same numbers close stderr.

``device`` holds what JAX reports (``platform``, ``kind``, ``count``),
``n_devices``, the devices the cell uses, ``memory_peak_bytes``, the
peak of the fullest of them, and ``memory_peak_bytes_per_device``, each
one's peak. With ``--trace 1`` it adds ``busy_s``, one device's busy
seconds (the mean over the cell's devices), and ``window_s``. The
trace's per-family runs (``devtrace.DeviceTrace.family_runs``) count
each batch once, however many devices ran it, and the roofline readers
divide its work by the cell's devices times one device's peak.
"""

from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import check  # noqa: E402
import corpus as corpus_mod  # noqa: E402
import load  # noqa: E402


TRACE_CAPACITY = 1 << 19  # spans a traced window may record
HOST_SPANS = ("pack", "compress", "decode")  # the executor's host work


class BenchFailure(RuntimeError):
    """The run cannot measure: no chip, a missing file, a bad entry."""


def process_start() -> float:
    """The process's start on the ``perf_counter`` clock (from /proc
    where it exists, else when this module was imported)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return T_IMPORT
    return min(T_IMPORT, time.perf_counter() - age)


# -- finding things by name ----------------------------------------------------
def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchFailure(f"no {path}")
    with open(path) as f:
        return json.load(f)


def load_json(kind: str, name: str, here: Path = HERE) -> dict:
    path = here / kind / f"{name}.json"
    if not path.is_file():
        raise BenchFailure(f"no {kind} file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, here: Path = HERE):
    """The module ``<here>/<kind>/<name>.py`` (names may hold dots)."""
    path = here / kind / f"{name}.py"
    if not path.is_file():
        raise BenchFailure(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    entry: dict
    config: dict
    traffic: dict
    metrics: list  # the BENCHMARK.json metric entries this run reports
    here: Path = HERE  # where its metric readers and kernel files live


def resolve_cell(bench: dict, name: str, trace: bool, root: Path = ROOT,
                 here: Path = HERE) -> Cell:
    """The cell ``name`` with its configuration, traffic and the metrics
    a ``--trace 0`` (end-to-end) or ``--trace 1`` (per-layer) run of it
    reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[entry["config"]]
    cfg_path = root / cfg_entry["file"]
    if not cfg_path.is_file():
        raise BenchFailure(f"no configuration file {cfg_path}")
    with open(cfg_path) as f:
        config = json.load(f)
    traffic = load_json("traffic", entry["traffic"], here)
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if trace:
        reported = {m["name"] for m in e2e}
        metrics = [m for m in bench["per_layer"]
                   if (name in m["workloads"] if "workloads" in m
                       else m["moves"] in reported)]
    else:
        metrics = e2e
    return Cell(name, entry, config, traffic, metrics, here)


# -- the device ------------------------------------------------------------------
def check_device(n_chips: int, platform: str = "tpu"):
    """The first ``n_chips`` JAX devices; fails unless they are
    ``platform`` devices (no fallback to another backend)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise BenchFailure(f"no {platform} device: JAX sees "
                           f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < n_chips:
        raise BenchFailure(f"need {n_chips} {platform} devices, JAX sees "
                           f"{len(devices)}")
    return devices[:n_chips]


def peaks_for(kind: str, here: Path = HERE) -> dict:
    with open(here / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise BenchFailure(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# -- what a run measured, as the metric readers see it ---------------------------
@dataclass
class RunData:
    """Everything one run measured. Metric readers (``metrics/*.py``)
    take a :class:`RunData` and return a number, or None when they find
    nothing to read."""

    cell: Cell
    setup_s: float
    loop: str                    # "open" or "closed"
    window_s: float              # first submit to the last answer
    attempted: int
    answered: int
    latencies_s: list            # due -> answer, every answered request
    queue_s: list                # the service's queue phase, per request
    counters: dict               # registry counters: change over the window
    spans: list = field(default_factory=list)   # service spans in the window
    device: object = None        # devtrace.DeviceTrace with --trace 1
    peaks: dict | None = None
    serve: dict = field(default_factory=dict)

    def kernel_work(self, family: str):
        return load_module("kernels", family, self.cell.here).work

    def host_us_per_query(self):
        """The executor's host work (``pack``, ``compress`` and
        ``decode`` spans, summed) over the answered queries, in µs."""
        if self.answered == 0:
            return None
        spent = sum(dur for name, _t0, dur, _d, _a in self.spans if name in HOST_SPANS)
        return 1e6 * spent / self.answered


def read_metrics(data: RunData) -> dict:
    out = {}
    for m in data.cell.metrics:
        value = load_module("metrics", m["name"], data.cell.here).read(data)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- one run ---------------------------------------------------------------------
def to_program(c):
    """The generated corpus as the program's input types."""
    from repro.core.lexicon import Lexicon
    from repro.data.corpus import TokenTable

    table = TokenTable(c.doc_ids, c.positions, c.lemma_ids, c.doc_lengths)
    lex = Lexicon.from_rank_counts(counts=c.counts, doc_freqs=c.doc_freqs,
                                   n_docs=c.n_docs, sw_count=c.sw_count,
                                   fu_count=c.fu_count)
    return table, lex


def make_pool(c, pool: dict, seed: int) -> list:
    kw = {k: pool[k] for k in ("min_len", "max_len", "window")}
    if pool["sampler"] == "stop":
        return corpus_mod.sample_stop_queries(c, pool["n_queries"], seed=seed, **kw)
    if pool["sampler"] == "mixed":
        return corpus_mod.sample_mixed_queries(c, pool["n_queries"], seed=seed, **kw)
    raise BenchFailure(f"unknown sampler {pool['sampler']!r}")


def generate(config: dict, traffic: dict, seed: int):
    """The corpus and the query pool of one seed. Every seed serves the
    same documents and the same pool -- drawn from the configuration's
    and the traffic's own seeds -- with the documents in the seed's
    order, so that the seed changes the order of the work and not its
    sizes."""
    cc = config["corpus"]
    base = corpus_mod.generate_corpus(cc["n_docs"], cc["mean_doc_len"],
                                      cc["vocab_size"], cc["zipf_alpha"],
                                      cc["sw_count"], cc["fu_count"], cc["seed"])
    pool = make_pool(base, traffic["pool"], traffic["pool"]["seed"])
    return corpus_mod.permute_docs(base, seed), pool


def serve_config(config: dict, trace: bool):
    from repro.serving import ServeConfig

    kw = dict(config["serve"])
    kw["buckets"] = tuple(kw["buckets"])
    # a traced run keeps every span of the window
    return ServeConfig(trace_enabled=trace, trace_capacity=TRACE_CAPACITY, **kw)


def drive(svc, pool, traffic: dict, seconds: float, seed: int) -> load.LoopRecord:
    """Drive the window. Requests ask the pool in shuffled cycles (each
    query once per cycle); open-loop arrivals are the traffic's schedule
    rotated by a time drawn from the seed."""
    arr = traffic["arrivals"]
    picks = load.shuffled_cycles(len(pool), np.random.default_rng(seed + 1))
    if arr["loop"] == "open":
        offsets = load.ARRIVALS[arr["process"]](
            arr["qps"], seconds, arr["seed"],
            **{k: v for k, v in arr.items()
               if k not in ("loop", "process", "qps", "seed")})
        offsets = load.rotate(offsets, seconds, np.random.default_rng(seed + 2))
        return load.run_open(svc, pool, offsets, [next(picks) for _ in offsets])
    if arr["loop"] == "closed":
        return load.run_closed(svc, pool, arr["callers"], seconds, picks)
    raise BenchFailure(f"unknown loop {arr['loop']!r}")


def counter_values(svc) -> dict:
    from repro.obs.metrics import Counter

    return {n: svc.metrics.get(n).value for n in svc.metrics.names()
            if isinstance(svc.metrics.get(n), Counter)}


def start_profiler(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def enable_compile_cache():
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    or where ``JAX_COMPILATION_CACHE_DIR`` says; returns its counters."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache(ROOT)
    # every executable goes to the cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


def build_service(cell: Cell, seed: int, devices, trace: bool, log=print):
    """Set-up of one run: the seed's corpus and pool, the program's
    index and ``SearchService``, every shape of the pool warmed, and the
    row cache filled by serving the pool once. Returns (corpus, pool,
    service)."""
    from repro.core.index_builder import build_index
    from repro.launch.mesh import mesh_on
    from repro.serving import SearchService

    config = cell.config
    t = time.perf_counter()
    c, pool = generate(config, cell.traffic, seed)
    table, lex = to_program(c)
    log(f"inputs: {c.n_docs} docs, {c.n_rows} tokens, {len(pool)} pool "
        f"queries, {time.perf_counter() - t:.3f}s")
    t = time.perf_counter()
    index = build_index(table, lex, max_distance=config["index"]["max_distance"])
    log(f"build: {time.perf_counter() - t:.3f}s")
    svc = SearchService(index, mesh_on(devices), serve_config(config, trace))
    t = time.perf_counter()
    n_exec = load.warm_service(svc, pool)
    log(f"warm: {n_exec} executables, {svc.compiled.stats['compiles']} "
        f"compiled or loaded, {time.perf_counter() - t:.3f}s")
    t = time.perf_counter()
    load.serve_pool_once(svc, pool)
    log(f"row cache: the pool served once, {time.perf_counter() - t:.3f}s")
    return c, pool, svc


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             platform: str = "tpu", t_start: float | None = None,
             keep_trace: str | None = None, use_cache: bool = False,
             log=print) -> dict:
    """One run of one cell; returns the result object (the last line).
    ``use_cache`` keeps compiled executables in JAX's persistent cache
    at ``<checkout>/.jax_cache`` (or where ``JAX_COMPILATION_CACHE_DIR``
    says). ``keep_trace`` names a directory that receives the raw
    profiler trace and its host side (``tests/bench/record_trace.py``)."""
    t_start = process_start() if t_start is None else t_start
    devices = check_device(cell.entry["chips"], platform)
    import jax

    cache = enable_compile_cache() if use_cache else None
    config, traffic = cell.config, cell.traffic
    peaks = peaks_for(devices[0].device_kind) if platform == "tpu" else None

    c, pool, svc = build_service(cell, seed, devices, trace, log)
    compiles0 = svc.compiled.stats["compiles"]
    if cache is not None:
        log(cache.line())

    # the device trace is a TPU's: on any other platform a traced run
    # reports the span metrics only
    profile = trace and devices[0].platform == "tpu"
    trace_dir = None
    if profile:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        start_profiler(trace_dir)
    svc.tracer.clear()
    counters0 = counter_values(svc)
    setup_s = time.perf_counter() - t_start
    if profile:
        with jax.profiler.TraceAnnotation("bench.window"):
            t_anchor = time.perf_counter()
            rec = drive(svc, pool, traffic, seconds, seed)
        jax.profiler.stop_trace()
    else:
        rec = drive(svc, pool, traffic, seconds, seed)
    counters1 = counter_values(svc)
    if svc.tracer.dropped:
        log(f"tracer dropped {svc.tracer.dropped} spans of the window")
    in_window = svc.compiled.stats["compiles"] - compiles0
    memory_peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                    for d in devices]

    # client-side latency: a response reaches its caller when the drain
    # that served it returns
    answered_at = stamp_answers(rec)
    lat, queue = [], []
    for r, t_ans in zip(rec.requests, answered_at):
        resp = r.ticket.response
        if resp is not None and resp.status in ("ok", "degraded"):
            lat.append(t_ans - r.due)
            queue.append(resp.phases.get("queue", 0.0))
    log(f"window: {len(rec.requests)} requests, {len(lat)} answered, "
        f"{rec.t_end - rec.t0:.3f}s; compiles inside {in_window}")
    drains = [(t1 - t0, t0 - rec.t0) for name, t0, t1 in rec.states if name == "drain"]
    longest = max(drains)
    log(f"drains: {len(drains)}, longest {longest[0]:.3f}s at "
        f"{longest[1]:.3f}s into the window")
    if traffic["arrivals"]["loop"] == "open":
        late = [r.submitted - r.due for r in rec.requests]
        log(f"generator late: p50 {1e3 * load.percentile(late, 50):.3f} ms, "
            f"p95 {1e3 * load.percentile(late, 95):.3f} ms, "
            f"max {1e3 * max(late):.3f} ms")
    spans = []
    if trace:
        spans = [(s.name, svc.tracer.epoch + s.ts, s.dur, s.depth, dict(s.args))
                 for s in svc.tracer.snapshot()]
    data = RunData(
        cell=cell, setup_s=setup_s, loop=traffic["arrivals"]["loop"],
        window_s=rec.t_end - rec.t0, attempted=len(rec.requests),
        answered=len(lat), latencies_s=lat, queue_s=queue,
        counters={k: counters1.get(k, 0) - counters0.get(k, 0) for k in counters1},
        spans=spans, peaks=peaks, serve=config["serve"])
    served = [(r.query, r.ticket.response) for r in rec.requests]
    states = rec.states
    result_device = {"platform": devices[0].platform,
                     "kind": devices[0].device_kind,
                     "count": len(jax.devices()),
                     "n_devices": len(devices),
                     "memory_peak_bytes": max(memory_peaks),
                     "memory_peak_bytes_per_device": memory_peaks}
    # free the program's state before the reference runs
    del svc, rec
    gc.collect()

    breakdown = None
    if profile:
        import devtrace

        t = time.perf_counter()
        try:
            dt = devtrace.reduce_dir(trace_dir, t_anchor, spans, states)
            log(f"trace reduction: {dt.n_devices} devices, "
                f"{time.perf_counter() - t:.3f}s")
        finally:
            if keep_trace:
                shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
                with open(os.path.join(keep_trace, "host.json"), "w") as f:
                    json.dump({"window": t_anchor,
                               "spans": spans, "states": states}, f, default=str)
            shutil.rmtree(trace_dir, ignore_errors=True)
        data.device = dt
        result_device["busy_s"] = dt.busy_s
        result_device["window_s"] = dt.window_s
        breakdown = {"device_ops": dt.top_ops[:10], "idle_gaps": dt.top_gaps[:10]}
    metrics = read_metrics(data)

    t = time.perf_counter()
    ref_numbers = check.check_run(c, config, pool, served, seed)
    log(f"reference: {ref_numbers['detail']}, {time.perf_counter() - t:.3f}s")
    for msg in ref_numbers["mismatches"][:5]:
        log(f"mismatch: {msg}")
    checks = ref_numbers["checks"]
    result = {
        "correct": check.passed(checks),
        "attempted": data.attempted,
        "failed": data.attempted - data.answered,
        "metrics": metrics,
        "device": result_device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def stamp_answers(rec: load.LoopRecord) -> list:
    """For every request, when the drain that answered it returned (the
    end of the first ``drain`` state after its submit); None if never."""
    ends = sorted(t1 for name, _t0, t1 in rec.states if name == "drain")
    out = []
    for r in rec.requests:
        k = int(np.searchsorted(ends, r.submitted, side="left"))
        out.append(ends[k] if k < len(ends) and r.ticket.done else None)
    return out


def main(argv=None) -> int:
    t_start = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        bench = load_benchmark()
        cell = resolve_cell(bench, args.workload, bool(args.trace))
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=t_start,
                          use_cache=True, log=log)
    except BenchFailure as e:
        log(f"run: FAILED: {e}")
        return 1
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['rule']} {c['limit']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
