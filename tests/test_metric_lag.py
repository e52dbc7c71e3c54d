"""A metric whose `update_dict` calls are deferred (`EvalMetric._defer`, what
`Module.fit` does inside its training loop) returns what the immediate
sequence returns, bit for bit, whenever anything looks; and the reader of
`fit.metric_lagged_share` on recorded step records."""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import metric as metric_mod

ROWS, CLASSES, BATCHES = 6, 4, 5


def _probs(rng, classes):
    p = rng.rand(ROWS, classes).astype(np.float32) + 0.05
    return p / p.sum(axis=1, keepdims=True)


def _classes(seed, classes=CLASSES):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, classes, ROWS).astype(np.float32),
             _probs(rng, classes)) for _ in range(BATCHES)]


def _values(seed):
    rng = np.random.RandomState(seed)
    return [(rng.randn(ROWS).astype(np.float32),
             rng.randn(ROWS).astype(np.float32)) for _ in range(BATCHES)]


def _mean_gap(label, pred):
    return float(np.abs(label - pred.argmax(axis=1)).sum()), label.shape[0]


# every class of the registry: how to make one, and batches it takes
CASES = {
    metric_mod.CompositeEvalMetric:
        (lambda: mx.metric.create(["acc", "ce", "f1"]), _classes(0, 2)),
    metric_mod.Accuracy: (lambda: mx.metric.create("acc"), _classes(1)),
    metric_mod.TopKAccuracy:
        (lambda: mx.metric.create("top_k_accuracy", top_k=2), _classes(2)),
    metric_mod.F1: (lambda: mx.metric.create("f1"), _classes(3, 2)),
    metric_mod.Perplexity:
        (lambda: mx.metric.Perplexity(ignore_label=0), _classes(4)),
    metric_mod.MAE: (lambda: mx.metric.create("mae"), _values(5)),
    metric_mod.MSE: (lambda: mx.metric.create("mse"), _values(6)),
    metric_mod.RMSE: (lambda: mx.metric.create("rmse"), _values(7)),
    metric_mod.CrossEntropy: (lambda: mx.metric.create("ce"), _classes(8)),
    metric_mod.NegativeLogLikelihood:
        (lambda: mx.metric.create("nll_loss"), _classes(9)),
    metric_mod.PearsonCorrelation:
        (lambda: mx.metric.create("pearsonr"), _values(10)),
    metric_mod.Loss: (lambda: mx.metric.create("loss"), _classes(11)),
    metric_mod.CustomMetric:
        (lambda: mx.metric.create(_mean_gap), _classes(12)),
}


def _feed(metric, batches):
    for label, pred in batches:
        metric.update_dict({"softmax_label": mx.nd.array(label)},
                           {"softmax_output": mx.nd.array(pred)})


def _queued(metric):
    return sum(len(m._pending)
               for m in getattr(metric, "metrics", [metric]))


def _same(got, want):
    """Equal to the bit, NaN included."""
    assert repr(got) == repr(want)


def test_every_class_of_the_registry_has_a_case():
    assert set(metric_mod._METRIC_REGISTRY.values()) == set(CASES)


@pytest.mark.parametrize("lag", [1, 3, BATCHES + 1])
@pytest.mark.parametrize("klass", list(CASES), ids=lambda k: k.__name__)
def test_a_lagged_sequence_reads_as_the_immediate_one(klass, lag):
    make, batches = CASES[klass]
    now, later = make(), make()
    _feed(now, batches)
    later._defer(lag)
    children = len(getattr(later, "metrics", [later]))
    for i, batch in enumerate(batches):
        _feed(later, [batch])
        assert _queued(later) == min(i + 1, lag) * children
    _same(later.get(), now.get())
    assert _queued(later) == 0
    # and it goes on from there
    _feed(now, batches[:2])
    _feed(later, batches[:2])
    _same(later.get_name_value(), now.get_name_value())


LOOKS = {"get": lambda m: m.get(), "get_name_value":
         lambda m: m.get_name_value(), "str": str,
         "sum_metric": lambda m: m.sum_metric,
         "num_inst": lambda m: m.num_inst}


@pytest.mark.parametrize("look", list(LOOKS))
@pytest.mark.parametrize("klass", [
    metric_mod.F1, metric_mod.Perplexity, metric_mod.CompositeEvalMetric,
    metric_mod.CustomMetric, metric_mod.CrossEntropy],
    ids=lambda k: k.__name__)
def test_every_look_folds_everything_first(klass, look):
    make, batches = CASES[klass]
    now, later = make(), make()
    _feed(now, batches)
    later._defer(len(batches))
    _feed(later, batches)
    # nothing has been folded: the sums are where `reset` left them
    assert all(m._num_inst == 0 and len(m._pending) == len(batches)
               for m in getattr(later, "metrics", [later]))
    if klass is metric_mod.CompositeEvalMetric and look in ("sum_metric",
                                                            "num_inst"):
        later = later.get_metric(1)    # a composite keeps no sums: a child's
        now = now.get_metric(1)
    _same(LOOKS[look](later), LOOKS[look](now))
    assert _queued(later) == 0


def test_micro_f1_reads_its_own_counts_after_the_fold():
    batches = CASES[metric_mod.F1][1]
    now, later = mx.metric.F1(average="micro"), mx.metric.F1(average="micro")
    _feed(now, batches)
    later._defer(2)
    _feed(later, batches)
    _same(later.get(), now.get())
    assert (later.tp, later.fp, later.fn) == (now.tp, now.fp, now.fn)


@pytest.mark.parametrize("klass", [metric_mod.CrossEntropy, metric_mod.F1,
                                   metric_mod.CompositeEvalMetric],
                         ids=lambda k: k.__name__)
def test_reset_drops_what_is_pending(klass):
    make, batches = CASES[klass]
    fresh, metric = make(), make()
    metric._defer(2)
    _feed(metric, batches[:2])
    assert _queued(metric) > 0
    metric.reset()
    assert _queued(metric) == 0
    _same(metric.get(), fresh.get())
    assert all(m.num_inst == 0 and m.sum_metric == 0.0
               for m in getattr(metric, "metrics", [metric]))
    _feed(metric, batches[2:])
    _feed(fresh, batches[2:])
    _same(metric.get(), fresh.get())


def test_an_entry_holds_the_arrays_not_the_holders():
    (label, pred), (label2, pred2) = CASES[metric_mod.CrossEntropy][1][:2]
    now = mx.metric.create("ce")
    _feed(now, [(label, pred)])
    later = mx.metric.create("ce")
    later._defer(1)
    holder, out = mx.nd.array(label), mx.nd.array(pred)
    later.update_dict({"softmax_label": holder}, {"softmax_output": out})
    # an iterator that recycles its batch, an executor that rebinds outputs
    holder[:] = label2
    out._data = mx.nd.array(pred2)._data
    _same(later.get(), now.get())


class CountsRows(mx.metric.EvalMetric):
    """A user's metric that overrides `update` alone."""

    def __init__(self):
        super().__init__("rows")
        self.calls = 0

    def update(self, labels, preds):
        self.calls += 1
        self.sum_metric += float(preds[0].asnumpy().sum())
        self.num_inst += labels[0].shape[0]


class OwnDict(CountsRows):
    """... and one that overrides `update_dict` without `super`."""

    def update_dict(self, label, pred):
        self.update(list(label.values()), list(pred.values()))


def test_a_subclass_is_deferred_through_update_and_not_past_update_dict():
    batches = CASES[metric_mod.CrossEntropy][1]
    mine, own = CountsRows(), OwnDict()
    for metric in (mine, own):
        metric._defer(1)
    for i, batch in enumerate(batches):
        _feed(mine, [batch])
        _feed(own, [batch])
        assert (mine.calls, own.calls) == (i, i + 1)
    _same(mine.get(), own.get())
    assert mine.calls == len(batches)


def test_update_called_directly_is_never_deferred():
    (label, pred) = CASES[metric_mod.CrossEntropy][1][0]
    metric = mx.metric.create("ce")
    metric._defer(4)
    metric.update([mx.nd.array(label)], [mx.nd.array(pred)])
    assert metric._num_inst == ROWS and not metric._pending


class SecondFails(CountsRows):
    def update(self, labels, preds):
        if self.calls == 1:
            self.calls += 1
            raise ValueError("bad batch")
        super().update(labels, preds)


def test_a_deferred_update_raises_at_the_fold_and_names_its_batch():
    batches = CASES[metric_mod.CrossEntropy][1]
    metric = SecondFails()
    metric._defer(1)
    _feed(metric, batches[:2])          # batch 0 folded, batch 1 queued
    with pytest.raises(ValueError, match="bad batch") as caught:
        _feed(metric, batches[2:3])     # folds batch 1
    assert any("batch 1 " in note for note in caught.value.__notes__)
    # the metric is not wedged: batch 2 is queued and folds on a read
    assert metric.get()[1] == pytest.approx(
        float(batches[0][1].sum() + batches[2][1].sum()) / (2 * ROWS))
    # a new span of deferring counts its batches from 0 again
    metric._defer(0)
    metric._defer(1)
    assert metric._batch == 0


def test_the_loop_counts_what_lagged_and_a_forced_fold_counts_in_neither():
    batches = CASES[metric_mod.CrossEntropy][1]
    metric = mx.metric.create("ce")
    assert metric._lag_counts() == (0, 0)
    _feed(metric, batches[:1])                  # lag 0: nothing is queued
    assert metric._lag_counts() == (0, 0)
    metric._defer(1)
    _feed(metric, batches[:1])
    assert metric._lag_counts() == (1, 0)
    _feed(metric, batches[1:3])
    assert metric._lag_counts() == (2, 2)
    metric.get()                                # forces batch 2
    _feed(metric, batches[3:4])
    assert metric._lag_counts() == (1, 0)
    both = mx.metric.create(["acc", "ce"])
    both._defer(1)
    _feed(both, batches[:2])
    assert both._lag_counts() == (4, 2)         # the children's, summed


SWEPT = [metric_mod.CrossEntropy, metric_mod.F1, metric_mod.Perplexity,
         metric_mod.CustomMetric, metric_mod.CompositeEvalMetric]


@pytest.mark.parametrize("klass", SWEPT, ids=lambda k: k.__name__)
def test_the_health_sweep_reads_what_is_folded_and_forces_nothing(klass):
    make, batches = CASES[klass]
    now, later = make(), make()
    later._defer(1)
    _feed(later, batches[:3])
    _feed(now, batches[:2])
    before = _queued(later)
    _same(later._name_value_as_folded(), now.get_name_value())
    assert _queued(later) == before > 0
    _feed(now, batches[2:3])
    _same(later.get_name_value(), now.get_name_value())
    # with nothing queued it is `get_name_value`, an empty metric's NaN too
    _same(later._name_value_as_folded(), now.get_name_value())
    later.reset(), now.reset()
    _same(later._name_value_as_folded(), now.get_name_value())


@pytest.mark.parametrize("lag", [1, 4])
@pytest.mark.parametrize("klass", SWEPT, ids=lambda k: k.__name__)
def test_the_health_sweep_passes_while_nothing_is_folded(klass, lag):
    """After a `reset` (an epoch's start, `Speedometer`'s) the next ``lag``
    updates are only queued: there is no value yet, and the sweep must not
    take `get`'s NaN for one."""
    make, batches = CASES[klass]
    metric = make()
    metric._defer(lag)
    for round_ in range(2):
        for n in range(lag):
            _feed(metric, batches[n:n + 1])
            assert metric._name_value_as_folded() == []
            assert _queued(metric) > 0
        _feed(metric, batches[lag:lag + 1])
        pairs = metric._name_value_as_folded()
        assert pairs and all(np.isfinite(v) for _, v in pairs)
        metric.reset()


def _fit_with_sweeps(k, frequent=2, batches=12):
    from mxnet_tpu import runprof, telemetry
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    rng = np.random.RandomState(0)
    x = rng.uniform(size=(8 * batches, 10)).astype(np.float32)
    y = rng.randint(0, 4, 8 * batches).astype(np.float32)
    seen = []
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(x, y, batch_size=8), num_epoch=2,
            eval_metric=mx.metric.create(["acc", "ce"]),
            batch_end_callback=[mx.callback.Speedometer(8, frequent),
                                lambda p: seen.append(p.nbatch)],
            batches_per_dispatch=k)
    return seen, runprof.snapshot()


@pytest.mark.parametrize("halt", ["0", "1"])
@pytest.mark.parametrize("k", [1, 4])
def test_a_healthy_fit_trips_no_sentinel_whatever_resets_the_metric(
        k, halt, monkeypatch):
    """`Speedometer` resets the metric every second batch and the sweep
    looks at every batch: right after a reset, and at an epoch's start,
    everything is queued and nothing folded. No anomaly, no dump, and
    ``MXNET_RUNPROF_HALT=1`` does not stop the run."""
    from mxnet_tpu import runprof, stepprof, telemetry
    monkeypatch.setenv("MXNET_RUNPROF_CHECK_EVERY", "1")
    monkeypatch.setenv("MXNET_RUNPROF_HALT", halt)
    # the sentinels of the METRIC are what is tested. The step-time spike
    # detector accuses a step four times the median of the eight before,
    # and these steps take under a millisecond: on a host that runs five
    # other test workers one of 24 is that late now and then (the driver's
    # run of PR 31: [1-0] failed, with K = 4 there are too few dispatches
    # for it to speak at all)
    monkeypatch.setenv("MXNET_RUNPROF_SPIKE_FACTOR", "0")
    telemetry.reset(), stepprof.reset(), runprof.reset()
    try:
        seen, snap = _fit_with_sweeps(k)
        assert seen == list(range(12)) * 2
        assert snap["anomaly_counts"] == {}
        assert telemetry.get_metric("run_anomalies_total",
                                    kind="nonfinite_loss") is None
        assert telemetry.get_metric("run_anomalies_total",
                                    kind="nonfinite_metric") is None
    finally:
        runprof.reset(), stepprof.reset(), telemetry.reset()


def test_the_sweep_still_sees_a_loss_that_is_not_finite(monkeypatch):
    """One dispatch behind, not blind: a NaN that has been folded trips the
    sentinel at the next sweep."""
    from mxnet_tpu import runprof, stepprof, telemetry
    from mxnet_tpu.module.base_module import _count_fit_batch
    monkeypatch.setenv("MXNET_RUNPROF_CHECK_EVERY", "1")
    telemetry.reset(), stepprof.reset(), runprof.reset()
    try:
        _, batches = CASES[metric_mod.CrossEntropy]
        metric = mx.metric.create("ce")
        metric._defer(1)
        label, pred = batches[0]
        batch = mx.io.DataBatch(data=[mx.nd.array(pred)], label=None)
        _feed(metric, [(label, pred * np.float32("nan"))])
        _count_fit_batch(batch, metric)         # queued only: no value yet
        assert runprof.snapshot()["anomaly_counts"] == {}
        _feed(metric, batches[1:2])             # folds the NaN batch
        _count_fit_batch(batch, metric)
        assert runprof.snapshot()["anomaly_counts"] == {"nonfinite_metric": 1}
    finally:
        runprof.reset(), stepprof.reset(), telemetry.reset()


# -- the reader of `fit.metric_lagged_share` on recorded step records -------

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
NAME = "fit.metric_lagged_share"


@pytest.fixture
def reader():
    from benchmark import run as harness
    return harness.load_module("layer_metrics", NAME)


def _step(entry, waits):
    """A `stepprof.timeline()` record entered at ``entry`` s on
    `perf_counter`; ``waits`` the attrs of its update_metric phases."""
    spans = [["h2d", 0.0, 0.001, {"bytes": 7}], ["dispatch", 0.001, 0.03, {}]]
    spans += [["device_compute", 0.04, 0.06, dict(a, via="update_metric")]
              for a in waits]
    return {"seq": int(entry * 10), "clock": [0, entry], "wall": 0.1,
            "batches": len(waits), "spans": spans}


LAG = {"queued": 1, "lagged": 1}
FORCED = {"queued": 1, "lagged": 0}


@pytest.mark.parametrize("steps, want", [
    # a callback that never reads: all but the epoch's first update lag
    ([_step(0, [FORCED])] + [_step(i, [LAG]) for i in range(1, 50)], 98.0),
    # one that reads every batch: every fold was forced
    ([_step(i, [FORCED]) for i in range(4)], 0.0),
    # dispatches of 8 with a read in the sixth: its own eight were forced
    ([_step(i, [LAG] * 8) for i in range(5)] + [_step(5, [FORCED] * 8)],
     100.0 * 40 / 48),
    # under a monitor nothing is queued
    ([_step(i, [{"queued": 0, "lagged": 0}]) for i in range(4)], None),
    # a program that reads the metric in the step that made it (the parent)
    ([_step(i, [{}]) for i in range(4)], None),
    ([], None),
])
def test_share_of_the_updates_that_lagged(reader, monkeypatch, steps, want):
    from benchmark import timeline
    assert reader.share(steps) == want

    class Run:
        result = {"t_open": -1.0, "t_close": 1e9}

    monkeypatch.setattr(timeline, "program_timeline", lambda: steps)
    assert reader.read(Run()) == want
    # only the window counts, and a program without a timeline reads None
    Run.result = {"t_open": 0.5, "t_close": 1e9}
    assert reader.read(Run()) == reader.share(steps[1:])
    monkeypatch.setattr(timeline, "program_timeline", lambda: None)
    assert reader.read(Run()) is None


def test_the_manifest_names_the_reader_and_the_cells():
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [m for m in doc["per_layer"] if m["name"] == NAME]
    # every cell whose entry drives `Module.fit` (all of them so far)
    assert entry["workloads"] == [w["name"] for w in doc["workloads"]]
    assert (entry["moves"], entry["source"], entry["better"], entry["unit"]) \
        == ("train_samples_per_s", "program_span", "higher", "%")
    assert entry["layer"] in {m["layer"] for m in doc["per_layer"]
                              if m is not entry}
