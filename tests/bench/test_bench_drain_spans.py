"""The readers of the drain's own spans and of the host->device byte
counter, on a synthetic run: their values, and None where a run has
nothing for them to read."""

import pytest

from bench_tiny import run

CELLS = {"open": "md5-frequent-poisson", "closed": "md9-mixed-closed"}

# (name, start, duration, depth, args), as run.py hands spans to readers
SPANS = [
    ("drain", 0.0, 0.0100, 0, {"requests": 4}),
    ("plan", 0.0, 0.0002, 1, {"n": 4}),
    ("group", 0.0002, 0.0001, 1, {}),
    ("schedule", 0.0003, 0.0003, 1, {}),
    ("batch", 0.0006, 0.0080, 1, {"family": "qt1", "B": 4, "bucket": 1024, "n": 4}),
    ("pack", 0.0006, 0.0020, 2, {"family": "qt1"}),
    ("transfer", 0.0016, 0.0010, 3, {"bytes": 98336}),
    ("execute", 0.0030, 0.0040, 2, {"kind": "base"}),
    ("decode", 0.0070, 0.0012, 2, {}),
    ("fetch", 0.0070, 0.0004, 3, {}),
    ("respond", 0.0086, 0.0008, 1, {"family": "qt1", "n": 4}),
    ("stats", 0.0094, 0.0006, 1, {}),
]
ANSWERED = 4


def _data(loop, spans=SPANS, counters=None, answered=ANSWERED):
    cell = run.resolve_cell(run.load_benchmark(), CELLS[loop], True)
    if counters is None:
        counters = {"serve.transfer.h2d_bytes": 98336, "cache.pack.hits": 8}
    return run.RunData(cell=cell, setup_s=1.0, loop=loop, window_s=1.0,
                       attempted=answered, answered=answered, latencies_s=[],
                       queue_s=[], counters=counters, spans=list(spans),
                       serve=cell.config["serve"])


def _read(name, data):
    return run.load_module("metrics", name).read(data)


@pytest.mark.parametrize("loop", sorted(CELLS))
def test_readers_values(loop):
    data = _data(loop)
    assert _read(f"transfer_us_per_query.{loop}", data) == pytest.approx(
        1e6 * (0.0010 + 0.0004) / ANSWERED)
    assert _read(f"h2d_bytes_per_query.{loop}", data) == pytest.approx(
        98336 / ANSWERED)
    assert _read(f"service_us_per_query.{loop}", data) == pytest.approx(
        1e6 * (0.0002 + 0.0001 + 0.0003 + 0.0008 + 0.0006) / ANSWERED)
    # the executor's host work keeps its meaning: transfer and fetch lie
    # inside pack and decode, which it already sums
    assert data.host_us_per_query() == pytest.approx(
        1e6 * (0.0020 + 0.0012) / ANSWERED)


@pytest.mark.parametrize("loop", sorted(CELLS))
def test_readers_none_when_nothing_answered(loop):
    data = _data(loop, answered=0)
    for metric in ("transfer_us_per_query", "h2d_bytes_per_query",
                   "service_us_per_query"):
        assert _read(f"{metric}.{loop}", data) is None


@pytest.mark.parametrize("loop", sorted(CELLS))
def test_readers_none_for_a_program_without_them(loop):
    """A program that records neither the new spans nor the counter (only
    ``plan``, ``group`` and the executor's spans) gives them nothing."""
    new = {"transfer", "fetch", "schedule", "respond", "stats"}
    data = _data(loop, spans=[s for s in SPANS if s[0] not in new],
                 counters={"cache.pack.hits": 8})
    for metric in ("transfer_us_per_query", "h2d_bytes_per_query",
                   "service_us_per_query"):
        assert _read(f"{metric}.{loop}", data) is None


def test_each_cell_lists_its_three_readers():
    bench = run.load_benchmark()
    for loop, cell in CELLS.items():
        names = {m["name"] for m in run.resolve_cell(bench, cell, True).metrics}
        assert {f"transfer_us_per_query.{loop}", f"h2d_bytes_per_query.{loop}",
                f"service_us_per_query.{loop}"} <= names
