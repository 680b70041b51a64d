"""Reduce a JAX profiler trace of one window to device metrics.

On a TPU the profiler writes one ``.xplane.pb``. Its ``/device:TPU:<n>``
planes carry an ``XLA Modules`` line (one event per executable run,
named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` / ``Async XLA Ops``
lines (one event per operation). The ``/host:CPU`` plane carries the
benchmark's ``bench.window`` annotation, which ties the profiler's
clock to the host's ``perf_counter`` clock: the annotation opens at
the instant the benchmark reads as the window's start. The service's
own spans (``batch`` with its family, B and L bucket; ``pack``,
``decode`` ...) and the loop's states (``submit``, ``drain``, ``wait``)
are on ``perf_counter``, so through the annotation every device event
can be put inside the batch that launched it and every idle gap beside
what the host was doing.

On n devices (``n_devices``, the trace's TPU planes):

* busy: on each device the union of the intervals in which an
  operation ran inside the window; ``busy_s`` is their mean, one
  device's busy time;
* per family: the device seconds of the executables each family's
  batches ran, as their mean over the devices (one device's time), and
  the (B, L) of each batch once, however many devices ran it, for the
  kernels' work count. A roofline reader divides a batch's whole work by
  n times one device's peak, so it reads a share of the n devices'
  combined peak;
* ``top_ops``: device operations by total time (``family/op``);
* ``top_gaps``: device-idle time inside the window, summed by what the
  host was doing (``drain/pack``, ``drain/decode``, ``wait`` ...).
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os
from dataclasses import dataclass, field

WINDOW = "bench.window"


@dataclass
class DeviceTrace:
    busy_s: float
    window_s: float
    n_devices: int = 1
    family_s: dict = field(default_factory=dict)      # family -> device seconds
    family_runs: dict = field(default_factory=dict)   # family -> [(B, L)], a batch once
    top_ops: list = field(default_factory=list)       # [[name, seconds], ...]
    top_gaps: list = field(default_factory=list)      # [[host state, seconds], ...]
    unplaced_runs: int = 0                            # module runs in no batch

    def idle_pct(self):
        """Share of the window in which no operation ran, in percent
        (None for an empty window)."""
        if self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {files}")
    return files[0]


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _op_name(event_name: str) -> str:
    """``%fusion.44 = s32[...] fusion(...)`` -> ``fusion.44``."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%")


class _Host:
    """What the host was doing at an instant (``perf_counter`` seconds):
    the deepest service span covering it, under the loop's state.

    The nested spans and states are flattened once into a piecewise-
    constant timeline of that label, so a lookup is one ``bisect``
    however long the window's longest span (a stalled drain) is."""

    def __init__(self, spans, states):
        ivs = [(t0, t0 + dur, depth, name) for name, t0, dur, depth, _a in spans]
        ivs += [(t0, t1, -1, name) for name, t0, t1 in states]
        ivs.sort()
        self.edges = sorted({x for a, b, _d, _n in ivs for x in (a, b)})
        # the label of [edges[i], edges[i+1]): of the intervals covering
        # it, the deepest, and of equally deep ones the last in ``ivs``
        self.labels = []
        heap, j = [], 0
        for p in self.edges:
            while j < len(ivs) and ivs[j][0] <= p:
                heapq.heappush(heap, (-ivs[j][2], -j))
                j += 1
            while heap and ivs[-heap[0][1]][1] <= p:
                heapq.heappop(heap)
            self.labels.append(self._name(ivs[-heap[0][1]]) if heap else "outside")

    @staticmethod
    def _name(iv) -> str:
        _a, _b, depth, name = iv
        if depth < 0 or name == "drain":
            return name
        return f"drain/{name}"

    def label(self, t: float) -> str:
        k = bisect.bisect_right(self.edges, t) - 1
        return self.labels[k] if k >= 0 else "outside"

    def split(self, a: float, b: float, pieces: int = 8) -> dict:
        """Seconds of [a, b] by host label (label at each piece's middle)."""
        out: dict = {}
        step = (b - a) / pieces
        for i in range(pieces):
            lab = self.label(a + (i + 0.5) * step)
            out[lab] = out.get(lab, 0.0) + step
        return out


def reduce_profile(pd, t_window: float, spans: list, states: list) -> DeviceTrace:
    """Reduce a loaded ``ProfileData``. ``spans`` are the service's spans
    as (name, start, duration, depth, args) and ``states`` the loop's
    (state, start, end), all on ``perf_counter``; ``t_window`` the
    instant the benchmark read as it opened the ``bench.window``
    annotation."""
    w0_ns = w1_ns = None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        w0_ns, w1_ns = ev.start_ns, ev.start_ns + ev.duration_ns
    if w0_ns is None:
        raise RuntimeError(f"no {WINDOW} annotation in the trace")
    off = t_window - w0_ns * 1e-9  # profiler ns -> perf_counter seconds
    batches = sorted((t0, t0 + dur, args) for name, t0, dur, _d, args in spans
                     if name == "batch" and "B" in args)
    b_starts = [b[0] for b in batches]
    host = _Host(spans, states)

    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise RuntimeError("no TPU device plane in the trace")
    busy_total = 0.0
    family_s: dict = {}
    family_runs: dict = {}
    runs_seen: set = set()  # (batch, ordinal on its plane): a run counted once
    op_s: dict = {}
    gap_s: dict = {}
    unplaced = 0
    for plane in devices:
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in lines.get("XLA Modules", []))
        fam_of_module = []
        runs_in_batch: dict = {}
        for a_ns, b_ns in modules:
            a, b = a_ns * 1e-9 + off, b_ns * 1e-9 + off
            k = bisect.bisect_right(b_starts, a) - 1
            if k >= 0 and batches[k][1] >= b:
                args = batches[k][2]
                fam = str(args["family"])
                family_s[fam] = family_s.get(fam, 0.0) + (b - a)
                key = (k, runs_in_batch.get(k, 0))
                runs_in_batch[k] = key[1] + 1
                if key not in runs_seen:  # else: the batch on another device
                    runs_seen.add(key)
                    family_runs.setdefault(fam, []).append(
                        (int(args["B"]), int(args["bucket"])))
                fam_of_module.append(fam)
            else:
                unplaced += 1
                fam_of_module.append("other")
        m_starts = [m[0] for m in modules]
        ops = []
        for line_name in ("XLA Ops", "Async XLA Ops"):
            for ev in lines.get(line_name, []):
                a_ns, b_ns = ev.start_ns, ev.start_ns + ev.duration_ns
                ops.append((a_ns, b_ns))
                if line_name == "XLA Ops":
                    k = bisect.bisect_right(m_starts, a_ns) - 1
                    fam = (fam_of_module[k] if k >= 0 and modules[k][1] >= a_ns
                           else "other")
                    name = f"{fam}/{_op_name(ev.name)}"
                    op_s[name] = op_s.get(name, 0.0) + ev.duration_ns * 1e-9
        busy = [(max(a, w0_ns), min(b, w1_ns)) for a, b in _union(ops)
                if b > w0_ns and a < w1_ns]
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edges = [w0_ns] + [x for iv in busy for x in iv] + [w1_ns]
        for a_ns, b_ns in zip(edges[0::2], edges[1::2]):
            if b_ns > a_ns:
                for lab, s in host.split(a_ns * 1e-9 + off, b_ns * 1e-9 + off).items():
                    gap_s[lab] = gap_s.get(lab, 0.0) + s
    n = len(devices)
    top_ops = sorted(([k, v / n] for k, v in op_s.items()), key=lambda x: -x[1])
    top_gaps = sorted(([k, v / n] for k, v in gap_s.items()), key=lambda x: -x[1])
    return DeviceTrace(
        busy_s=busy_total / n, window_s=(w1_ns - w0_ns) * 1e-9, n_devices=n,
        family_s={k: v / n for k, v in family_s.items()},
        family_runs=family_runs, top_ops=top_ops, top_gaps=top_gaps,
        unplaced_runs=unplaced)


def reduce_dir(log_dir: str, t_window: float, spans: list,
               states: list) -> DeviceTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(log_dir))
    return reduce_profile(pd, t_window, spans, states)
