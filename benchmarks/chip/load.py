"""Traffic: arrival schedules and the loops that drive ``SearchService``.

Copies of the sound parts of the program's ``serving/load.py`` --
``poisson_arrivals``, ``bursty_arrivals``, the backdated open-loop
replay, ``warm_service`` and the nearest-rank percentile -- kept here
so that no later change to the program can move the yardstick, plus
the closed loop of a fixed number of callers.

Both loops run in the calling thread. The open loop submits every
request whose scheduled instant has passed, backdated to that instant
(``submit(..., arrival=)``), then drains; it records how late it
submitted each request, so a starved generator is not read as a fast
server. The closed loop keeps ``callers`` requests outstanding: each
drain answers every outstanding request and each caller then sends its
next one. Every loop records what the host was doing when, as
``(state, t0, t1)`` intervals on the ``perf_counter`` clock: ``submit``,
``drain`` or ``wait``.
"""

from __future__ import annotations

import random
import time
from dataclasses import dataclass, field


def poisson_arrivals(qps: float, duration_s: float, seed: int = 0) -> list[float]:
    """Offsets (seconds from the start) of a Poisson process at rate
    ``qps``, truncated to ``duration_s``; i.i.d. exponential gaps."""
    if qps <= 0:
        raise ValueError(f"qps must be positive (got {qps})")
    rng = random.Random(seed)
    out, t = [], 0.0
    while True:
        t += rng.expovariate(qps)
        if t >= duration_s:
            return out
        out.append(t)


def bursty_arrivals(qps: float, duration_s: float, seed: int = 0,
                    burst_factor: float = 3.0, mean_on_s: float = 0.25,
                    mean_off_s: float = 0.75) -> list[float]:
    """Offsets of an on/off Markov-modulated Poisson process whose
    long-run rate is ``qps``: ``burst_factor × qps`` while on, the
    residual rate that keeps the average while off."""
    if qps <= 0:
        raise ValueError(f"qps must be positive (got {qps})")
    if burst_factor < 1.0:
        raise ValueError(f"burst_factor must be >= 1 (got {burst_factor})")
    qps_on = qps * burst_factor
    qps_off = max(0.0, (qps * (mean_on_s + mean_off_s) - qps_on * mean_on_s)
                  / mean_off_s)
    rng = random.Random(seed)
    out, t, on = [], 0.0, True
    while t < duration_s:
        dwell = rng.expovariate(1.0 / (mean_on_s if on else mean_off_s))
        end = min(t + dwell, duration_s)
        rate = qps_on if on else qps_off
        if rate > 0:
            tt = t
            while True:
                tt += rng.expovariate(rate)
                if tt >= end:
                    break
                out.append(tt)
        t, on = end, not on
    return out


ARRIVALS = {"poisson": poisson_arrivals, "bursty": bursty_arrivals}


def rotate(offsets, duration_s: float, rng) -> list[float]:
    """The schedule shifted cyclically by a time drawn from ``rng``: the
    same arrivals and gaps (one gap wraps round), in another order."""
    shift = float(rng.uniform(0.0, duration_s))
    return sorted((t + shift) % duration_s for t in offsets)


def shuffled_cycles(n: int, rng):
    """Pool indices, every index once per cycle, each cycle in a new
    order drawn from ``rng``; endless."""
    while True:
        yield from (int(i) for i in rng.permutation(n))


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty sequence."""
    vs = sorted(values)
    if not vs:
        raise ValueError("percentile of no values")
    k = min(len(vs) - 1, max(0, int(round(q / 100.0 * (len(vs) - 1)))))
    return vs[k]


def warm_service(service, queries) -> int:
    """Serve one representative query of every compiled (step family,
    L-bucket) group the pool routes to at every B of the batch ladder,
    so that every executable the traffic can reach is built (or loaded
    from the compile cache) before the window. Returns the number of
    executables."""
    reps: dict[tuple, list] = {}
    for q in queries:
        p = service.explain(q)
        if p.is_compiled:
            reps.setdefault((p.step_family, p.bucket), q)
    ladder, B = [], 1
    while B <= service.config.max_batch:
        ladder.append(B)
        B *= 2
    for q in reps.values():
        for B in ladder:
            for _ in range(B):
                service.submit(q)
            service.drain()
    return service.compiled.n_executables


def serve_pool_once(service, queries) -> None:
    """Serve every query of the pool once, in batches of the service's
    largest B, so that the window meets the row cache as a server of
    this pool holds it: every row the pool asks for already packed."""
    step = service.config.max_batch
    for i in range(0, len(queries), step):
        for q in queries[i:i + step]:
            service.submit(q)
        service.drain()


@dataclass
class Request:
    """One request of the window: which pool query, when it was due,
    when it was submitted, and its ticket."""

    query: int
    due: float
    submitted: float
    ticket: object


@dataclass
class LoopRecord:
    """What one loop did: its requests, the window's bounds on the
    ``perf_counter`` clock, and the host's state intervals."""

    requests: list = field(default_factory=list)
    t0: float = 0.0
    t_end: float = 0.0   # when the last request was answered
    states: list = field(default_factory=list)

    def _state(self, name: str, t0: float, t1: float) -> None:
        self.states.append((name, t0, t1))


def run_open(service, pool, offsets, picks, *, idle_sleep_s=0.0005) -> LoopRecord:
    """Replay ``offsets`` (seconds from the start) open-loop; request i
    asks ``pool[picks[i]]``. Arrivals never wait for the service."""
    rec = LoopRecord()
    t0 = rec.t0 = time.perf_counter()
    i, n = 0, len(offsets)
    while i < n:
        ta = time.perf_counter()
        due = False
        while i < n and t0 + offsets[i] <= ta:
            now = time.perf_counter()
            q = picks[i]
            rec.requests.append(Request(q, t0 + offsets[i], now, service.submit(
                pool[q], arrival=t0 + offsets[i])))
            i += 1
            due = True
        tb = time.perf_counter()
        if due:
            rec._state("submit", ta, tb)
            service.drain()
            rec._state("drain", tb, time.perf_counter())
        elif i < n:
            time.sleep(min(idle_sleep_s, max(0.0, t0 + offsets[i] - tb)))
            rec._state("wait", tb, time.perf_counter())
    ta = time.perf_counter()
    service.drain()
    rec.t_end = time.perf_counter()
    rec._state("drain", ta, rec.t_end)
    return rec


def run_closed(service, pool, callers: int, seconds: float, picks) -> LoopRecord:
    """``callers`` callers, each sending its next query (the next index
    from ``picks``) as soon as its reply arrives, until ``seconds`` have
    passed; the last drain that starts inside the window is waited for."""
    rec = LoopRecord()
    t0 = rec.t0 = time.perf_counter()
    t_close = t0 + seconds
    ta = t0
    for _ in range(callers):
        q = next(picks)
        now = time.perf_counter()
        rec.requests.append(Request(q, now, now, service.submit(pool[q])))
    while True:
        tb = time.perf_counter()
        rec._state("submit", ta, tb)
        service.drain()
        ta = time.perf_counter()
        rec._state("drain", tb, ta)
        if ta >= t_close:
            break
        for _ in range(callers):
            q = next(picks)
            now = time.perf_counter()
            rec.requests.append(Request(q, now, now, service.submit(pool[q])))
    rec.t_end = ta
    return rec
