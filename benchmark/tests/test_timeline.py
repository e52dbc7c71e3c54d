"""The join of the program's step timeline with the device trace
(`benchmark/timeline.py`), on a hand-built pair with a known answer in every
bucket and on a pair recorded on the chip:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_timeline.py -q

``data/tiny_pair.xplane.pb`` and ``data/tiny_pair.timeline.json`` come from
one traced run of `vgg16_fit` at the tiny size (`tests/tiny.py`) on the chip
(TPU v5 lite; my chip run, PR 24; `python -m benchmark.tests.record_pair`)."""
import json
import os

import pytest

from benchmark import reduce, run as harness, timeline

HERE = os.path.dirname(os.path.abspath(__file__))
PAIR_TRACE = os.path.join(HERE, "data", "tiny_pair.xplane.pb")
PAIR_STEPS = os.path.join(HERE, "data", "tiny_pair.timeline.json")
STEP = r"^jit_step\("
ZERO = 1_790_000_000_000_000_000   # the profile's start, Unix ns
MS = 1e6


def step(seq, entry_ms, spans, wall_ms, batches=1, perf=500.0):
    """A `stepprof.timeline()` record entered ``entry_ms`` after the
    profile's start; ``spans`` as (name, start ms, duration ms, attrs)."""
    return {"seq": seq, "clock": [ZERO + int(entry_ms * MS),
                                  perf + entry_ms / 1e3],
            "wall": wall_ms / 1e3, "other": 0.0, "batches": batches,
            "synced": False, "phases": {},
            "spans": [[n, s / 1e3, d / 1e3, a] for n, s, d, a in spans]}


READ = {"via": "update_metric"}


def fit_step(seq, entry_ms):
    """One step of 100 ms as `_fit_loop` records it: stage 0-10, dispatch
    10-14, data_wait 14-15, read-back wait 15-90, callbacks to 100."""
    return step(seq, entry_ms, [("h2d", 0, 10, {"bytes": 7}),
                                ("dispatch", 10, 4, {}),
                                ("data_wait", 14, 1, {}),
                                ("device_compute", 15, 75, READ)], 100)


def hand_trace(runs, hi_ms, gaps_ms):
    """What `reduce.reduce` gives, from step-program executions and gaps in
    ms: the stretch runs from the first start to ``hi_ms``."""
    lo = runs[0][0] * MS
    return {"lo": lo, "hi": hi_ms * MS, "window_s": (hi_ms * MS - lo) / 1e9,
            "dispatches": len(runs), "step_program": STEP,
            "chips": [{"modules": [{"name": "jit_step(1)", "start": a * MS,
                                    "end": b * MS} for a, b in runs]
                       + [{"name": "jit__unstack(2)", "start": runs[0][1] * MS,
                           "end": runs[0][1] * MS + 10}],
                       "gaps": [(a * MS, b * MS) for a, b in gaps_ms]}]}


def placed(steps):
    return [timeline.place(s, ZERO) for s in steps]


def test_every_bucket_gets_its_known_share():
    # steps enter at 0, 100, 200, 300; each step program starts 30 ms after
    # its step's entry (16 ms after the call returned) and runs 58 ms, so
    # the device is idle from 88 of one step to 30 of the next
    steps = [fit_step(i, 100.0 * i) for i in range(4)]
    trace = hand_trace([(30, 88), (130, 188), (230, 288)], 330,
                       [(88, 130), (188, 230), (288, 330)])
    joined = timeline.attribute(placed(steps), trace)
    idle = {b: ns / MS for b, ns in joined["idle_ns"].items()}
    # of each 42 ms: 2 of read-back wait with the device done, 10 of
    # callbacks, 10 of staging, 4 of dispatch, 16 enqueued; data_wait
    # (14-15) lies under the enqueued stretch, which takes precedence
    assert idle == pytest.approx({"readback": 6, "other": 30, "stage": 30,
                                  "dispatch": 12, "enqueued": 48,
                                  "data_wait": 0})
    assert sum(idle.values()) == pytest.approx(3 * 42)
    assert joined["unmatched"] == 0
    proof = joined["proof"]
    assert proof["executions"] == 3 and proof["steps_inside"] == 3
    assert [t / MS for t in proof["readback_tail_ns"]] == \
        pytest.approx([2, 2, 2])
    assert [t / MS for t in proof["enqueued_wait_ns"]] == \
        pytest.approx([16] * 4)


def test_host_phase_covers_what_no_enqueue_claims():
    # the iterator blocks for 30 ms BEFORE the dispatch (the scan path's
    # order), while the device is idle: that idle time is data_wait's
    spans = [("data_wait", 0, 30, {}), ("h2d", 30, 5, {}),
             ("dispatch", 35, 5, {}), ("device_compute", 40, 50, READ),
             ("device_compute", 92, 3, READ)]
    steps = [step(i, 100.0 * i, spans, 100, batches=2) for i in range(3)]
    trace = hand_trace([(41, 85), (141, 185)], 241, [(85, 141), (185, 241)])
    joined = timeline.attribute(placed(steps), trace)
    idle = {b: ns / MS for b, ns in joined["idle_ns"].items()}
    # 85-90 read-back, 90-92 nobody's, 92-95 read-back, 95-100 callbacks,
    # then 30 data_wait, 5 stage, 5 dispatch, 1 enqueued
    assert idle == pytest.approx({"readback": 16, "other": 14,
                                  "data_wait": 60, "stage": 10,
                                  "dispatch": 10, "enqueued": 2})
    assert joined["unmatched"] == 0


@pytest.mark.parametrize("shift_ms, rule", [
    (+25.0, "before_dispatch"),    # the host's line lies too late: the
    # step program seems to start before its step has called it
    (-5.0, "after_readback"),      # too early: the outputs seem to be
    # on the host before the device has finished
])
def test_a_clock_that_is_off_is_counted(shift_ms, rule):
    steps = [fit_step(i, 100.0 * i + shift_ms) for i in range(-1, 5)]
    trace = hand_trace([(30, 88), (130, 188), (230, 288)], 330,
                       [(88, 130), (188, 230), (288, 330)])
    joined = timeline.attribute(placed(steps), trace)
    assert joined["proof"][rule] >= 3
    assert joined["unmatched"] >= 3
    other = {"before_dispatch": "after_readback",
             "after_readback": "before_dispatch"}[rule]
    assert joined["proof"][other] == 0


def test_a_step_the_trace_does_not_show_is_counted():
    # two steps entered in a stretch that holds one dispatch
    steps = [fit_step(i, 50.0 * i) for i in range(8)]
    trace = hand_trace([(130, 188)], 230, [(188, 230)])
    joined = timeline.attribute(placed(steps), trace)
    assert joined["proof"]["steps_inside"] == 2
    assert joined["unmatched"] >= 1


def test_a_timeline_that_does_not_reach_back_gives_none():
    trace = hand_trace([(30, 88), (130, 188)], 230, [(88, 130), (188, 230)])
    late = [fit_step(i, 100.0 * i) for i in range(1, 4)]   # ring lost step 0
    assert timeline.attribute(placed(late), trace) is None
    short = [fit_step(i, 100.0 * i) for i in range(2)]     # ends at 200
    assert timeline.attribute(placed(short), trace) is None
    assert timeline.attribute([], trace) is None


def test_per_batch_medians_tile_the_step():
    steps = [step(i, 100.0 * i, [("h2d", 0, 8, {}), ("dispatch", 8, 4, {}),
                                 ("data_wait", 12, 2, {}),
                                 ("device_compute", 14, 60 + i, READ),
                                 ("sync", 80, 5, {})], 100, batches=2)
             for i in range(5)]
    ms = timeline.per_batch_ms(steps)
    assert ms == pytest.approx({"stage": 4, "dispatch": 2, "data_wait": 1,
                                "readback": 31, "other": 12})
    assert sum(ms.values()) == pytest.approx(50)
    assert timeline.per_batch_ms([]) is None
    # records fed without a clock, and steps outside the window, drop out
    fed = dict(steps[0], clock=None)
    kept = timeline.window_steps(steps + [fed], 500.05, 500.35)
    assert [s["seq"] for s in kept] == [1, 2, 3]


class FakeRun:
    def __init__(self, trace_data=None):
        self.result = {"t_open": 0.0, "t_close": 1e12}
        self.trace_data, self.trace_dir = trace_data, "/nonexistent"
        self.lines = []
        self.log = self.lines.append


def test_readers_return_none_rather_than_guess(monkeypatch):
    names = [m["name"] for m in json.load(open(os.path.join(
        HERE, "..", "..", "BENCHMARK.json")))["per_layer"][-12:]]
    assert len(names) == 12 and "timeline.unmatched_steps" in names
    readers = {n: harness.load_module("layer_metrics", n) for n in names}
    # a program without `stepprof.timeline` (the parent commit): nothing
    monkeypatch.setattr(timeline, "program_timeline", lambda: None)
    run = FakeRun()
    assert [r.read(run) for r in readers.values()] == [None] * 12
    # a timeline and no trace: the host's medians, no shares, no count
    monkeypatch.setattr(timeline, "program_timeline",
                        lambda: [fit_step(i, 100.0 * i) for i in range(4)])
    run = FakeRun()
    got = {n: r.read(run) for n, r in readers.items()}
    assert got["fit.stage_ms"] == pytest.approx(10)
    assert got["dispatch.host_ms"] == pytest.approx(4)
    assert got["fit.data_wait_ms"] == pytest.approx(1)
    assert got["fit.readback_wait_ms"] == pytest.approx(75)
    assert got["fit.other_ms"] == pytest.approx(10)
    assert all(got[n] is None for n in names
               if n.startswith("idle.") or n.startswith("timeline."))
    # a trace whose file is gone: the same, and it says so
    run = FakeRun(hand_trace([(30, 88), (130, 188)], 230, [(88, 130)]))
    assert readers["idle.enqueued_share"].read(run) is None
    assert any("profile_start_time" in line for line in run.lines)


@pytest.fixture(scope="module")
def pair():
    if not os.path.exists(PAIR_TRACE):
        pytest.skip("no recorded pair")
    doc = json.load(open(PAIR_STEPS))
    return doc, reduce.reduce(PAIR_TRACE, STEP)


def test_recorded_pair_buckets_sum_to_the_idle_time(pair):
    doc, trace = pair
    start, stop = timeline.profile_times(PAIR_TRACE)
    assert start == doc["profile_start_time"] and stop > start
    steps = [timeline.place(s, start) for s in doc["steps"]]
    joined = timeline.attribute(steps, trace)
    assert joined is not None and joined["unmatched"] == 0
    idle_s = sum(joined["idle_ns"].values()) / 1e9
    assert idle_s == pytest.approx(trace["window_s"] - trace["busy_s"],
                                   rel=1e-9)
    assert all(ns >= 0 for ns in joined["idle_ns"].values())
    proof = joined["proof"]
    assert proof["executions"] == proof["steps_inside"] == trace["dispatches"]
    # the outputs are on the host after the device is done, never before
    assert min(proof["readback_tail_ns"]) > 0
    # what the run itself printed for the same pair
    shares = {b: ns / (trace["window_s"] * 1e9) * 100
              for b, ns in joined["idle_ns"].items()}
    assert shares == pytest.approx(doc["idle_share"], rel=1e-6)


def test_recorded_pair_through_the_readers(pair, monkeypatch, tmp_path):
    doc, trace = pair
    where = tmp_path / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    os.symlink(PAIR_TRACE, where / "t.xplane.pb")
    monkeypatch.setattr(timeline, "program_timeline", lambda: doc["steps"])
    run = FakeRun(trace)
    run.trace_dir = str(tmp_path)
    joined = timeline.join(run)
    assert joined["unmatched"] == 0
    idle = (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
    assert sum(joined["idle_share"].values()) == pytest.approx(idle, abs=1e-6)
    assert timeline.read(run, "timeline.unmatched_steps") == 0
    assert timeline.read(run, "idle.enqueued_share") == \
        joined["idle_share"]["enqueued"]
    assert len([line for line in run.lines if "timeline:" in line]) >= 3
