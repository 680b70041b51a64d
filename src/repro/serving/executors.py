"""Executors: the device/scalar execution layer of the serving tier
(DESIGN.md §14), instrumented per phase (DESIGN.md §15).

Two implementations of one :class:`Executor` protocol sit below the
:class:`repro.serving.service.SearchService` facade:

* :class:`CompiledExecutor` owns the serve-step factories and the
  per-(step kind, B, L) **executable table** — every distinct compiled
  shape ever executed, the denominator of the response-time guarantee.
  It implements dispatch-aware batching (the ROADMAP item): a ``qt34``
  group whose plan fits the QT5 step's non-stop slots is packed with
  zero stop constraints and served on the ``qt5`` executable of the
  same (B, L) — ``qt5_join`` with zero stop constraints *is*
  ``qt34_join`` — so mixed traffic compiles one executable ladder
  where it previously compiled two.
* :class:`ScalarExecutor` wraps the scalar
  :class:`repro.core.search.ProximitySearchEngine` — the correctness
  backstop every ``scalar``-route plan of the dispatch matrix falls
  back to (routing affects latency, never results).

Observability contract (§15): both executors record into the service's
shared :class:`repro.obs.MetricsRegistry` and :class:`repro.obs.Tracer`.
Every batch emits a span tree (``batch`` → ``pack`` / ``compress`` /
``compile`` / ``dispatch`` / ``execute`` / ``decode``; the host→device
copy is a ``transfer`` span inside ``pack`` or ``compress``, the
device→host copy a ``fetch`` span inside ``decode``) and every
:class:`ExecResult` carries the same timings as a ``phases`` dict whose
values tile ``[started_at, finished_at]`` exactly — the service adds
queue/plan on top, which is how a response's phase breakdown sums to
its end-to-end latency. Compile time is split from run time by
first-call detection: the first execution of a (kind, B, L) triple
ahead-of-time lowers and compiles the step (timed as the ``compile``
phase, with the XLA ``cost_analysis()`` summary captured off the
compiled executable); subsequent calls hit the AOT table, so the
``serve.step.<family>.B<B>.L<L>`` histograms measure pure run time —
the measured-cost table ``explain(costs=True)`` and admission control
calibrate ``est_step_cost`` against.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Protocol

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.jax_search import (
    assemble_qt1_compressed,
    assemble_qt2_compressed,
    assemble_qt34_compressed,
    assemble_qt5_compressed,
    batch_size_bucket,
    compress_qt1_batch,
    compress_qt2_batch,
    compress_qt34_batch,
    compress_qt5_batch,
    decode_results,
    make_qt1_serve_step,
    make_qt1_serve_step_compressed,
    make_wv_serve_step,
    pack_qt1_batch,
    pack_qt2_batch,
    pack_qt34_batch,
    pack_qt5_batch,
)
from repro.obs import MetricsRegistry, Tracer
from repro.serving.planner import (
    PAYLOAD_DELTA16,
    PAYLOAD_OFFSETS,
    PAYLOAD_RAW,
    delta16_aligned,
)

# the batch-level phases every ExecResult reports; the service prepends
# "queue" and "plan" (tests assert this exact vocabulary)
BATCH_PHASES = ("pack", "compress", "compile", "dispatch", "execute", "decode")


def zero_phases() -> dict:
    return {p: 0.0 for p in BATCH_PHASES}


@dataclass
class ExecResult:
    """Per-request execution record: the decoded results plus the
    executed shape — ``payload`` is the format actually served (a
    planner delta16 prediction downgrades to offsets when a key's
    in-block span overflows uint16), ``latency_s`` the wall-clock of
    the whole batch the request rode in, ``started_at``/``finished_at``
    the perf_counter timestamps of *that batch* (not the whole group:
    the service derives queue waits and deadline verdicts per batch),
    and ``phases`` the batch's per-phase durations in seconds —
    contiguous sub-intervals tiling [started_at, finished_at]."""

    results: dict
    latency_s: float
    bucket: int
    batch_size: int
    payload: str | None = None
    started_at: float = 0.0
    finished_at: float = 0.0
    phases: dict = field(default_factory=zero_phases)


class Executor(Protocol):
    """One (route, bucket) group of requests in, one ExecResult per
    request out, aligned with the inputs."""

    def execute(self, index, queries: list, selections: list, *,
                step_family: str | None, bucket: int | None,
                shared: list | None = None) -> list[ExecResult]: ...


# kind suffix -> planner payload name
_PAYLOAD_OF_KIND = {"base": PAYLOAD_RAW, "raw": PAYLOAD_RAW,
                    "delta": PAYLOAD_DELTA16, "offsets": PAYLOAD_OFFSETS}


def _payload_of_kind(kind: str) -> str:
    return _PAYLOAD_OF_KIND[kind.rsplit("_", 1)[-1] if "_" in kind else kind]


def xla_cost_summary(compiled) -> dict:
    """The interesting scalars of an XLA ``cost_analysis()`` dict —
    flops, bytes accessed, transcendentals — under underscore names."""
    ca = compiled.cost_analysis()
    return {key.replace(" ", "_"): float(ca[key])
            for key in ("flops", "bytes accessed", "transcendentals",
                        "optimal_seconds")
            if key in ca}


class CompiledExecutor:
    """Packs, compresses and executes padded batches on the compiled
    per-(step kind, B-bucket, L-bucket) serve steps.

    ``executables`` maps every (kind, B, L) triple ever executed to its
    batch count — the engine-stats surface tests assert B-bucket
    sharing on; ``stats["shared_batches"]`` counts qt34 groups served
    on qt5 executables. ``compile_times`` / ``cost_summaries`` hold the
    first-call AOT compile wall-clock and XLA cost_analysis summary per
    triple; measured run times stream into the metrics registry as
    ``serve.step.<family>.B<B>.L<L>`` histograms (µs)."""

    def __init__(self, mesh, config, pack_cache=None, compressed_cache=None,
                 metrics: MetricsRegistry | None = None,
                 tracer: Tracer | None = None, costs=None):
        self.mesh = mesh
        self.config = config
        # Pallas kernels compile for a TPU mesh; on any other backend
        # the interpreter is the only way they run
        self.interpret = mesh.devices.flat[0].platform != "tpu"
        self.pack_cache = pack_cache
        self.compressed_cache = compressed_cache
        # optional PayloadCostModel (owned by the service): warm batch
        # times stream into it per (family, bucket, payload arm), and
        # the planner consults it for the group's payload choice
        self.costs = costs
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        # compiled steps, one per (step family, payload format); jit
        # caches per (B, L) shape under each, and batch_size_bucket
        # bounds how many shapes each one ever sees
        self._steps: dict[str, object] = {}
        self.executables: dict[tuple, int] = {}
        # (kind, B, L) -> AOT-compiled executable, built on first
        # execution of the triple
        self._aot: dict[tuple, object] = {}
        self.compile_times: dict[tuple, float] = {}
        self.cost_summaries: dict[tuple, dict] = {}
        # (family, B, L) triples with measured run-time histograms
        self.measured_keys: set[tuple] = set()
        # delta-format eligibility on the cache-less compressed path is
        # static per (family, bucket) and goes sticky-False after a
        # uint16 span overflow so persistent-overflow corpora don't pay
        # a failed delta encoding per batch (with the compressed cache
        # the verdict is per key instead)
        self._delta_ok: dict[tuple, bool] = {}
        self.stats = {"batches": 0, "compressed_batches": 0,
                      "offset_fallbacks": 0, "shared_batches": 0,
                      "compiles": 0}

    @property
    def n_executables(self) -> int:
        return len(self.executables)

    def _step(self, kind: str, max_distance: int):
        step = self._steps.get(kind)
        if step is None:
            cfg = self.config
            if kind == "base":
                step = make_qt1_serve_step(self.mesh, top_k=cfg.top_k)
            elif kind in ("delta", "offsets"):
                step = make_qt1_serve_step_compressed(
                    self.mesh, top_k=cfg.top_k, delta_g=(kind == "delta")
                )
            else:  # "qt2_raw" ... "qt5_offsets"
                qtype, payload = kind.split("_", 1)
                step = make_wv_serve_step(
                    self.mesh, qtype, top_k=cfg.top_k, payload=payload,
                    max_distance=max_distance, r_max=cfg.r_max,
                    use_pallas=cfg.use_pallas, interpret=self.interpret,
                )
            self._steps[kind] = step
        return step

    def _family_fns(self, family: str):
        """(assemble_fn, pack_fn, compress_fn, kind prefix, K kwargs)
        for one step family — the only place the four families differ."""
        cfg = self.config
        if family == "qt1":
            return (assemble_qt1_compressed, pack_qt1_batch,
                    compress_qt1_batch, "", {"K": cfg.k_fst})
        if family == "qt2":
            return (assemble_qt2_compressed, pack_qt2_batch,
                    compress_qt2_batch, "qt2_", {"K": cfg.k_wv})
        if family == "qt34":
            return (assemble_qt34_compressed, pack_qt34_batch,
                    compress_qt34_batch, "qt34_", {"Kn": cfg.k_ord})
        return (assemble_qt5_compressed, pack_qt5_batch,
                compress_qt5_batch, "qt5_", {"Kn": cfg.k_ns, "Ks": cfg.k_st})

    def execute(self, index, queries, selections, *, step_family, bucket,
                shared=None, payload=None):
        """Serve one (step family, L-bucket) group: chunked to
        ``config.max_batch``, each chunk padded to the power-of-two
        batch ladder and executed on the (kind, B, L) executable.
        ``shared`` (aligned with ``queries``) flags requests riding a
        foreign step family — qt34 plans converted to zero-stop qt5
        plans by the caller; a batch containing any counts as shared.
        ``payload`` is the group's planner-chosen format: ``raw`` on a
        compressed engine forces the raw pack path (the cost model's
        raw arm); None keeps the config-static behavior."""
        cfg = self.config
        out: list[ExecResult] = []
        for lo in range(0, len(queries), cfg.max_batch):
            chunk_q = queries[lo:lo + cfg.max_batch]
            chunk_s = selections[lo:lo + cfg.max_batch]
            B_pad = batch_size_bucket(len(chunk_q), cfg.max_batch)
            pad = B_pad - len(chunk_q)
            # the batch span also holds the batch's bookkeeping below,
            # so a drain has no unnamed host time between its batches
            with self.tracer.span("batch", family=step_family, bucket=bucket,
                                  B=B_pad, n=len(chunk_q)) as bsp:
                t0 = time.perf_counter()
                kind, stub, args, t_pack, t_comp = self._prepare(
                    index, step_family, bucket,
                    chunk_q + [[]] * pad, chunk_s + [None] * pad, t0,
                    payload=payload,
                )
                key = (kind, B_pad, bucket)
                fn, first = self._executable_for(key, kind,
                                                 index.max_distance, args)
                t_compile = time.perf_counter()
                with self.tracer.span("dispatch", kind=kind):
                    raw = fn(*args)
                t_disp = time.perf_counter()
                with self.tracer.span("execute", kind=kind, compile=first):
                    raw = jax.block_until_ready(raw)
                t_exec = time.perf_counter()
                with self.tracer.span("decode"):
                    with self.tracer.span("fetch"):
                        raw = tuple(np.asarray(r) for r in raw)
                    decoded = decode_results(stub, *raw)
                t1 = time.perf_counter()
                bsp.set(kind=kind, compile=first)
                phases = {
                    "pack": t_pack - t0,
                    "compress": t_comp - t_pack,
                    "compile": t_compile - t_comp,
                    "dispatch": t_disp - t_compile,
                    "execute": t_exec - t_disp,
                    "decode": t1 - t_exec,
                }
                self.stats["batches"] += 1
                if shared is not None and any(shared[lo:lo + cfg.max_batch]):
                    self.stats["shared_batches"] += 1
                self.executables[key] = self.executables.get(key, 0) + 1
                if not first:
                    self._observe_warm(step_family, B_pad, bucket, kind,
                                       t_exec - t_compile,
                                       (t1 - t0) - phases["compile"])
                payload = _payload_of_kind(kind)
                out.extend(
                    ExecResult(results=decoded[bi], latency_s=t1 - t0,
                               bucket=bucket, batch_size=len(chunk_q),
                               payload=payload, started_at=t0, finished_at=t1,
                               phases=dict(phases))
                    for bi in range(len(chunk_q))
                )
        return out

    def _observe_warm(self, family, B, L, kind, step_s, warm_s) -> None:
        """Record one warm batch: its run-only step time (dispatch +
        device execute) and its whole wall-clock, compile excluded."""
        self.metrics.observe(f"serve.step.{family}.B{B}.L{L}", step_s * 1e6)
        # whole warm batch wall-clock (host pack/compress/decode
        # included): what one more batch of the shape actually costs
        # the serving loop — the admission predictor's primitive; the
        # step metric alone under-predicts it badly on host-bound
        # small batches
        self.metrics.observe(f"serve.batch.{family}.B{B}.L{L}", warm_s * 1e6)
        self.measured_keys.add((family, B, L))
        if self.costs is not None:
            # payload arbitration sees the whole warm batch cost
            # (pack/compress/decode included — host encode work counts
            # against the arm that incurs it), per padded query
            self.costs.observe(family, L, _payload_of_kind(kind),
                               warm_s * 1e6 / B)

    # -- compile-vs-run split ----------------------------------------------
    def _executable_for(self, key, kind, max_distance, args):
        """The executable for one (kind, B, L) triple. First call per
        triple AOT-lowers and compiles the step (the ``compile`` phase)
        and captures its XLA cost_analysis summary; later calls return
        the cached executable, so their step timings are pure run. A
        step the compiler refuses raises here, in the compile phase."""
        fn = self._aot.get(key)
        if fn is not None:
            return fn, False
        step = self._step(kind, max_distance)
        with self.tracer.span("compile", kind=kind, B=key[1], L=key[2]):
            t0 = time.perf_counter()
            fn = step.lower(*args).compile()
            self.cost_summaries[key] = xla_cost_summary(fn)
            dt = time.perf_counter() - t0
        self._aot[key] = fn
        self.compile_times[key] = dt
        self.stats["compiles"] += 1
        self.metrics.observe(
            f"serve.compile.{kind}.B{key[1]}.L{key[2]}", dt * 1e6)
        return fn, True

    # -- measured-cost surface ---------------------------------------------
    def measured_step_us(self, family: str, B: int, L: int) -> float | None:
        """Measured warm batch run time (p50 µs) for one (family, B, L)
        shape — the admission controller's prediction primitive
        (DESIGN.md §17). Falls back from the exact shape to the nearest
        measured shape of the family scaled by the slot ratio
        ``(B*L) / (B'*L')`` (step work is linear in both axes); None
        when the family has no measurement at all (the caller then uses
        the unit estimate)."""
        return self._nearest_p50("serve.step", family, B, L)

    def measured_batch_us(self, family: str, B: int, L: int) -> float | None:
        """Measured warm *whole-batch* wall-clock (p50 µs, host
        pack/compress/decode included, compile excluded) for one
        (family, B, L) shape — what one more batch of the shape costs
        the serving loop, and therefore what admission control and EDF
        splitting must predict with (the run-only step metric
        under-predicts host-bound small batches badly). Same
        nearest-shape fallback as :meth:`measured_step_us`."""
        return self._nearest_p50("serve.batch", family, B, L)

    def _nearest_p50(self, metric: str, family: str, B: int,
                     L: int) -> float | None:
        hist = self.metrics.get(f"{metric}.{family}.B{B}.L{L}")
        if hist is not None and hist.count:
            return hist.percentile(50)
        best = None
        for (fam, Bm, Lm) in self.measured_keys:
            if fam != family:
                continue
            h = self.metrics.get(f"{metric}.{fam}.B{Bm}.L{Lm}")
            if h is None or not h.count:
                continue
            # prefer the measured shape closest in slot count
            dist = abs(Bm * Lm - B * L)
            if best is None or dist < best[0]:
                best = (dist, h.percentile(50) * (B * L) / (Bm * Lm))
        return best[1] if best is not None else None

    def is_warm(self, family: str, B: int, L: int) -> bool:
        """Whether some executable of the family already exists at
        (B, L) — a batch routed to a cold shape pays the first-call AOT
        compile, which admission prediction must price in."""
        return any(kb == B and kl == L and _kind_family(kind) == family
                   for (kind, kb, kl) in self._aot)

    def family_warm(self, family: str, L: int) -> bool:
        """Whether the family has *any* warm B at this L-bucket. The
        admission predictor amortizes the compile penalty once this
        holds (a new B-bucket of an already-serving (family, L) pays
        one compile over the service lifetime; pricing it into every
        singleton admit cold-rejects all traffic a drain would happily
        batch onto the warm shapes — a self-sustaining reject spiral,
        since what is never admitted never warms)."""
        return any(kl == L and _kind_family(kind) == family
                   for (kind, _kb, kl) in self._aot)

    def compile_penalty_s(self) -> float:
        """Predicted first-call compile cost for a cold (kind, B, L)
        shape: the mean of the observed AOT compile times (0.0 before
        any compile has run — a cold service has nothing better, and
        the unit step estimate dominates its predictions anyway)."""
        if not self.compile_times:
            return 0.0
        return sum(self.compile_times.values()) / len(self.compile_times)

    def measured_scalar_us(self) -> float | None:
        """Measured per-request p50 of the scalar backstop engine."""
        hist = self.metrics.get("serve.step.scalar")
        if hist is not None and hist.count:
            return hist.percentile(50)
        return None

    def measured_cost(self, family: str, bucket: int) -> dict:
        """Measured run-time percentiles for every B-bucket of one
        (step_family, L-bucket) executable, plus its compile time and
        XLA cost summary — the calibration table for ``est_step_cost``
        (µs; empty until a second batch of the shape has run)."""
        out = {}
        for (fam, B, L) in sorted(self.measured_keys):
            if fam != family or L != bucket:
                continue
            hist = self.metrics.get(f"serve.step.{fam}.B{B}.L{L}")
            if hist is None or hist.count == 0:
                continue
            snap = hist.snapshot()
            entry = {"measured_p50_us": snap["p50"],
                     "measured_p95_us": snap["p95"],
                     "measured_p99_us": snap["p99"],
                     "count": snap["count"]}
            for (kind, kb, kl), dt in self.compile_times.items():
                if kb == B and kl == L and _kind_family(kind) == fam:
                    entry["compile_us"] = dt * 1e6
                    xla = self.cost_summaries.get((kind, kb, kl))
                    if xla:
                        entry["xla"] = xla
                    break
            out[f"B{B}"] = entry
        return out

    def est_vs_measured(self, streams_of) -> dict:
        """est_step_cost calibration: per measured (family, B, L), the
        planner's estimate (padded posting slots) against the measured
        run-time p50 — ``us_per_kslot`` is the live conversion factor
        admission control needs to turn an estimate into a time budget."""
        cfg = self.config
        out = {}
        for (fam, B, L) in sorted(self.measured_keys):
            hist = self.metrics.get(f"serve.step.{fam}.B{B}.L{L}")
            if hist is None or hist.count == 0:
                continue
            est = streams_of(fam, cfg) * L * cfg.doc_shards
            p50 = hist.percentile(50)
            out[f"{fam}/B{B}/L{L}"] = {
                "est_step_cost": est,
                "measured_p50_us": p50,
                "n": hist.count,
                "us_per_kslot": p50 / (est / 1000.0),
            }
        return out

    # -- batch preparation --------------------------------------------------
    def _prepare(self, index, family, bucket, queries, selections, t0,
                 payload=None):
        """Pack (and compress) one padded batch; returns
        ``(kind, decode stub, device args, t_pack_end, t_compress_end)``
        so the caller can tile the phase timeline without gaps.
        ``payload=PAYLOAD_RAW`` forces the raw pack path even on a
        compressed engine — the cost model's raw arm; the raw and
        compressed steps of a family are bit-identical in results, so
        the choice only moves time."""
        assemble_fn, pack_fn, compress_fn, prefix, kw = self._family_fns(family)
        cfg = self.config
        ccache = self.compressed_cache
        serve_compressed = cfg.compressed and payload != PAYLOAD_RAW
        if serve_compressed and ccache is not None:
            # the per-key compressed-row cache derives raw + compressed
            # rows in one pass, so pack and compress are one phase here
            # (attributed to pack; compress reads 0)
            with self.tracer.span("pack", family=family, cached=True):
                kind, host, stub = assemble_fn(
                    index, queries, L=bucket, doc_shards=cfg.doc_shards,
                    ccache=ccache, cache=self.pack_cache, plans=selections,
                    **kw,
                )
                args = self._transfer(host)
            self._count_compressed(kind)
            t_pack = time.perf_counter()
            return kind, stub, args, t_pack, t_pack
        if not serve_compressed:
            kind = "base" if family == "qt1" else f"{family}_raw"
            with self.tracer.span("pack", family=family):
                batch = pack_fn(
                    index, queries, L=bucket, doc_shards=cfg.doc_shards,
                    cache=self.pack_cache, plans=selections, **kw,
                )
                # the host->device transfer of the packed rows belongs
                # to pack, not to whatever phase is timed next
                args = self._transfer(batch.host_args())
            t_pack = time.perf_counter()
            return kind, batch, args, t_pack, t_pack
        with self.tracer.span("pack", family=family):
            batch = pack_fn(
                index, queries, L=bucket, doc_shards=cfg.doc_shards,
                cache=self.pack_cache, plans=selections, **kw,
            )
        t_pack = time.perf_counter()
        with self.tracer.span("compress", family=family):
            kind, host = self._compress_batch(bucket, batch, compress_fn,
                                              prefix)
            args = self._transfer(host)
        return kind, batch, args, t_pack, time.perf_counter()

    def _transfer(self, host_args) -> tuple:
        """Copy one batch's step arguments to the device (the
        ``transfer`` span), counting the bytes sent in
        ``serve.transfer.h2d_bytes``. The span times the host's calls;
        what the runtime finishes after they return lands in
        ``execute``."""
        nbytes = sum(a.nbytes for a in host_args)
        with self.tracer.span("transfer", bytes=nbytes):
            args = tuple(jnp.asarray(a) for a in host_args)
        self.metrics.inc("serve.transfer.h2d_bytes", nbytes)
        return args

    def _compress_batch(self, bucket, batch, compress_fn, prefix=""):
        """Cache-less compressed path: whole-batch re-encode with the
        per-(family, bucket) sticky delta verdict (the
        use_compressed_cache=False fallback, kept for benchmarking)."""
        ck = (prefix, bucket)
        ok = self._delta_ok.get(ck)
        if ok is None:
            ok = delta16_aligned(bucket, self.config)
            self._delta_ok[ck] = ok
        kind = "offsets"
        if ok:
            try:
                args = compress_fn(batch, delta_g=True)
                kind = "delta"
            except ValueError:  # in-block key span overflows uint16
                self._delta_ok[ck] = False
        if kind == "offsets":
            args = compress_fn(batch, delta_g=False)
        self._count_compressed(kind)
        return prefix + kind, args

    def _count_compressed(self, kind: str) -> None:
        self.stats["compressed_batches"] += 1
        if kind.endswith("offsets"):
            self.stats["offset_fallbacks"] += 1


def _kind_family(kind: str) -> str:
    """Step-kind -> step-family name ("base"/"delta"/"offsets" are the
    qt1 payload kinds; everything else is "<family>_<payload>")."""
    return kind.split("_", 1)[0] if "_" in kind else "qt1"


class ScalarExecutor:
    """The scalar correctness backstop: wraps a per-snapshot
    :class:`ProximitySearchEngine` behind the same Executor protocol —
    every dispatch-matrix shape the static-shape steps cannot express
    is served here, bit-identical to the reference the compiled paths
    are tested against. Responses carry the same timing surface as the
    compiled path (started_at/finished_at + a phase breakdown whose
    work all lands in ``execute``), so scalar-fallback traffic is
    first-class in deadline and phase accounting."""

    def __init__(self, config, metrics: MetricsRegistry | None = None,
                 tracer: Tracer | None = None):
        self.config = config
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        self._engine = None  # rebuilt per snapshot on first use

    def _engine_for(self, index):
        from repro.core.search import ProximitySearchEngine

        if self._engine is None or self._engine.index is not index:
            self._engine = ProximitySearchEngine(
                index, top_k=self.config.top_k, equalize_mode="bulk"
            )
        return self._engine

    def execute(self, index, queries, selections, *, step_family=None,
                bucket=None, shared=None):
        eng = self._engine_for(index)
        out = []
        with self.tracer.span("batch", family="scalar", n=len(queries)):
            for q in queries:
                t0 = time.perf_counter()
                with self.tracer.span("execute", kind="scalar"):
                    res, _ = eng.search_ids(list(q))
                t1 = time.perf_counter()
                self.metrics.observe("serve.step.scalar", (t1 - t0) * 1e6)
                phases = zero_phases()
                phases["execute"] = t1 - t0
                out.append(ExecResult(
                    results={"doc": res.doc, "start": res.start,
                             "end": res.end, "score": res.score},
                    latency_s=t1 - t0, bucket=0, batch_size=1,
                    started_at=t0, finished_at=t1, phases=phases,
                ))
        return out


def empty_results() -> dict:
    """A zero-hit result set with freshly allocated arrays — callers
    may mutate their response in place, so empty responses must never
    share buffers (the old module-level ``_EMPTY_RESULT`` dict handed
    the same four arrays to every empty response)."""
    return {"doc": np.zeros(0, np.int64), "start": np.zeros(0, np.int64),
            "end": np.zeros(0, np.int64), "score": np.zeros(0, np.float32)}
