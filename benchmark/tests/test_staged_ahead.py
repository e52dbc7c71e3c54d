"""The reader of `fit.staged_ahead_share`
(`benchmark/layer_metrics/fit.staged_ahead_share.py`) on recorded step
records, and the timeline's readers chosen by name:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_staged_ahead.py -q
"""
import json
import os

import pytest

from benchmark import run as harness, timeline
from benchmark.tests.test_timeline import FakeRun, fit_step, step

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "fit.staged_ahead_share"
BATCH = 77_071_360


def staged_step(seq, bound, ahead, prepared=None):
    """A step as `_fit_loop` records it once `Module.prepare` stages: the
    consumer's `h2d` with both counts, then `prepare`'s own."""
    spans = [("h2d", 0, 1, {"bytes": bound, "staged_ahead": ahead}),
             ("dispatch", 1, 4, {}), ("data_wait", 5, 1, {})]
    if prepared is not None:
        spans.append(("h2d", 6, 1, {"via": "prepare", "bytes": prepared}))
    return step(seq, 100.0 * seq, spans, 100)


@pytest.fixture
def reader():
    return harness.load_module("layer_metrics", NAME)


@pytest.mark.parametrize("steps, want", [
    # nothing was found ready: every batch staged by its own step
    ([staged_step(i, BATCH, 0) for i in range(4)], 0.0),
    # everything was
    ([staged_step(i, BATCH, BATCH, BATCH) for i in range(4)], 100.0),
    # an epoch's first step stages its own batch
    ([staged_step(0, BATCH, 0, BATCH)]
     + [staged_step(i, BATCH, BATCH, BATCH) for i in range(1, 4)], 75.0),
    # the scan path: one span for a dispatch of 8, and `prepare`'s own
    # bytes are not counted twice
    ([staged_step(0, 8 * BATCH, 6 * BATCH, BATCH)], 75.0),
    # a program that records no `staged_ahead` (the parent): nothing
    ([fit_step(i, 100.0 * i) for i in range(4)], None),
    ([], None),
])
def test_share_of_the_bytes_found_ready(reader, monkeypatch, steps, want):
    assert reader.share(steps) == want
    monkeypatch.setattr(timeline, "program_timeline", lambda: steps)
    assert reader.read(FakeRun()) == want


def test_only_the_window_counts(reader, monkeypatch):
    steps = [staged_step(0, BATCH, 0)] + \
        [staged_step(i, BATCH, BATCH) for i in range(1, 5)]
    monkeypatch.setattr(timeline, "program_timeline", lambda: steps)
    run = FakeRun()
    run.result = {"t_open": 500.15, "t_close": 500.45}   # steps 2, 3, 4
    assert reader.read(run) == 100.0
    # a program without `stepprof.timeline`
    monkeypatch.setattr(timeline, "program_timeline", lambda: None)
    assert reader.read(run) is None


def test_the_manifest_names_the_reader_and_the_cells():
    doc = json.load(open(os.path.join(HERE, "..", "..", "BENCHMARK.json")))
    entry = [m for m in doc["per_layer"] if m["name"] == NAME]
    assert len(entry) == 1
    assert entry[0]["workloads"] == [w["name"] for w in doc["workloads"]]
    assert entry[0]["moves"] == "train_samples_per_s"


def test_timeline_readers_by_name_return_none_rather_than_guess(monkeypatch):
    """`test_timeline.test_readers_return_none_rather_than_guess` takes the
    manifest's last twelve entries, which held PR 24's readers until this
    one was appended; the same check with the readers chosen by name."""
    doc = json.load(open(os.path.join(HERE, "..", "..", "BENCHMARK.json")))
    names = [m["name"] for m in doc["per_layer"]
             if m["name"] in timeline.PER_BATCH
             or m["name"].startswith(("idle.", "timeline."))]
    assert len(names) == 12
    readers = {n: harness.load_module("layer_metrics", n) for n in names}
    monkeypatch.setattr(timeline, "program_timeline", lambda: None)
    assert [r.read(FakeRun()) for r in readers.values()] == [None] * 12
    monkeypatch.setattr(timeline, "program_timeline",
                        lambda: [fit_step(i, 100.0 * i) for i in range(4)])
    run = FakeRun()
    got = {n: r.read(run) for n, r in readers.items()}
    assert got["fit.stage_ms"] == pytest.approx(10)
    assert got["dispatch.host_ms"] == pytest.approx(4)
    assert got["fit.other_ms"] == pytest.approx(10)
    assert all(got[n] is None for n in names
               if n.startswith(("idle.", "timeline.")))
