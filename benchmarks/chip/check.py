"""The comparison that decides ``correct``.

After the window has closed, the check draws from the seed a sample of
the distinct queries the window answered -- up to ``queries_per_group``
from every (route, L-bucket) group the responses report, so that every
route the traffic took is covered -- and compares *every* response of
those queries with the plain reference (``reference.py``). The numbers
compared, each against its limit:

* ``mismatched``: checked responses that differ from the reference
  (limit 0: the comparison is exact);
* ``unanswered``: requests of the window without a served response
  (limit 0);
* ``checked``: responses compared (at least 1).

The control (:func:`control_results`) puts the reference computed at
MaxDistance - 1 in the program's place: the index a smaller
MaxDistance would build, which breaks the configuration's guarantee
that every match within MaxDistance is found.
"""

from __future__ import annotations

import numpy as np

from reference import Reference, check_response

SERVED = ("ok", "degraded")


def passed(checks: dict) -> bool:
    ok = True
    for c in checks.values():
        if c["rule"] == "<=":
            ok &= c["value"] <= c["limit"]
        else:
            ok &= c["value"] >= c["limit"]
    return bool(ok)


def sample_queries(served: list, per_group: int, seed: int) -> list:
    """Pool indices to check: up to ``per_group`` distinct queries of
    each (path, bucket) group of the served responses, drawn from the
    seed."""
    groups: dict = {}
    for q, resp in served:
        if resp is not None and resp.status in SERVED:
            groups.setdefault((resp.path, resp.bucket), set()).add(q)
    rng = np.random.default_rng(seed + 303)
    chosen = set()
    for key in sorted(groups, key=str):
        qs = sorted(groups[key])
        pick = rng.permutation(len(qs))[:per_group]
        chosen.update(qs[i] for i in pick)
    return sorted(chosen)


def compare(ref: Reference, pool: list, served: list, chosen, top_k: int,
            answer=None) -> tuple[dict, list]:
    """Compare every served response of the ``chosen`` pool queries with
    the reference. ``answer(q, resp)`` replaces the response's results
    (the control); None compares what the program served."""
    chosen = set(chosen)
    lanes = {q: ref.lanes(pool[q]) for q in sorted(chosen)}
    mismatched = checked = unanswered = 0
    messages = []
    for q, resp in served:
        if resp is None or resp.status not in SERVED:
            unanswered += 1
            continue
        if q not in chosen:
            continue
        distinct = resp.path == "cpu"
        results = resp.results if answer is None else answer(q, resp)
        err = check_response(lanes[q], results, top_k, distinct)
        checked += 1
        if err is not None:
            mismatched += 1
            if len(messages) < 20:
                messages.append(f"query {pool[q]} via {resp.path}@L{resp.bucket}: {err}")
    checks = {
        "mismatched": {"value": mismatched, "limit": 0, "rule": "<="},
        "unanswered": {"value": unanswered, "limit": 0, "rule": "<="},
        "checked": {"value": checked, "limit": 1, "rule": ">="},
    }
    return checks, messages


def check_run(corpus, config: dict, pool: list, served: list, seed: int,
              answer=None) -> dict:
    """The check of one run: its numbers (``checks``), the first
    mismatches, and a one-line summary."""
    ref = Reference(corpus, config["index"]["max_distance"])
    chosen = sample_queries(served, config["check"]["queries_per_group"], seed)
    checks, messages = compare(ref, pool, served, chosen,
                               config["serve"]["top_k"], answer)
    detail = (f"{len(chosen)} sampled queries, {checks['checked']['value']} "
              f"responses compared, {checks['mismatched']['value']} differ")
    return {"checks": checks, "mismatches": messages, "detail": detail}


def control_results(ref_low: Reference, pool: list, top_k: int):
    """The control's answer function: the top-k lanes of the reference
    at a smaller MaxDistance, by relevance then anchor order (distinct
    fragments where the program's response came from the scalar route)."""
    cache: dict = {}

    def answer(q, resp):
        distinct = resp.path == "cpu"
        key = (q, distinct)
        if key not in cache:
            lanes = ref_low.lanes(pool[q])
            idx = np.argsort(-lanes.score, kind="stable")
            if distinct:
                seen, keep = set(), []
                for i in idx.tolist():
                    fr = (int(lanes.doc[i]), int(lanes.start[i]), int(lanes.end[i]))
                    if fr not in seen:
                        seen.add(fr)
                        keep.append(i)
                idx = np.asarray(keep, np.int64)
            idx = idx[:top_k]
            cache[key] = {"doc": lanes.doc[idx], "start": lanes.start[idx],
                          "end": lanes.end[idx], "score": lanes.score[idx]}
        return cache[key]

    return answer
