"""`Module.fit` places its input one dispatch ahead of the step that
consumes it (`Module.prepare`), and nothing else runs ahead.

The module is bound to another host device than the one the batches live
on (`mx.tpu(1)` is host device 1 in CPU mode, the batches are on
`mx.cpu()`), so that placing a batch is a transfer here as it is on the
chip; the `context=[several]` cases take the sharded placement."""
import logging
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import stepprof
from mxnet_tpu.base import device_of

ROWS, WIDTH, CLASSES = 8, 10, 4
ONE = "one device"
DP = "context=[four]"


def _contexts(where):
    return mx.tpu(1) if where == ONE else [mx.tpu(i) for i in range(4)]


def _net():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _params():
    rng = np.random.RandomState(3)
    shapes = {"fc1_weight": (16, WIDTH), "fc1_bias": (16,),
              "fc2_weight": (CLASSES, 16), "fc2_bias": (CLASSES,)}
    return {n: mx.nd.array(rng.randn(*s).astype(np.float32) * 0.3)
            for n, s in shapes.items()}


def _arrays(n, rows=ROWS, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(rows, WIDTH).astype(np.float32),
             rng.randint(0, CLASSES, rows).astype(np.float32))
            for _ in range(n)]


def _batch(x, y):
    return mx.io.DataBatch(data=[mx.nd.array(x, ctx=mx.cpu())],
                           label=[mx.nd.array(y, ctx=mx.cpu())])


class ListIter(mx.io.DataIter):
    """Hands out ``batches`` in order and counts what it has handed out."""

    def __init__(self, batches):
        first = batches[0]
        super().__init__(first.data[0].shape[0])
        self.batches, self.handed = batches, 0
        self.provide_data = [mx.io.DataDesc("data", first.data[0].shape)]
        self.provide_label = [mx.io.DataDesc("softmax_label",
                                             first.label[0].shape)]

    def reset(self):
        self.handed = 0

    def __next__(self):
        if self.handed == len(self.batches):
            raise StopIteration
        self.handed += 1
        return self.hand_out(self.handed - 1)

    next = __next__

    def hand_out(self, i):
        return self.batches[i]


class SwapIter(ListIter):
    """Hands out a ring of ``ring`` `DataBatch` objects and swaps the arrays
    of each as it goes out again: the objects' identity says nothing about
    their contents."""

    def __init__(self, arrays, ring):
        super().__init__([_batch(x, y) for x, y in arrays])
        self.ring = [_batch(*arrays[0]) for _ in range(ring)]

    def hand_out(self, i):
        batch = self.ring[i % len(self.ring)]
        batch.data, batch.label = self.batches[i].data, self.batches[i].label
        return batch


SGD = {"learning_rate": 0.1, "momentum": 0.9}


def _module(where, shape=(ROWS, WIDTH)):
    mod = mx.mod.Module(_net(), context=_contexts(where))
    mod.bind(data_shapes=[("data", shape)],
             label_shapes=[("softmax_label", shape[:1])])
    mod.init_params(arg_params=_params())
    mod.init_optimizer(optimizer="sgd", optimizer_params=SGD)
    return mod


def _state(mod):
    """Parameters and momenta on the host."""
    args, _ = mod.get_params()
    out = {n: a.asnumpy() for n, a in args.items()}
    for i, name in enumerate(mod._param_names):
        out["mom:" + name] = mod._updater.states[i].asnumpy()
    return out


def _hand_loop(where, arrays):
    """`_step` over the batches by hand: the state, the outputs and the
    metric after every step."""
    mod = _module(where, arrays[0][0].shape)
    metric = mx.metric.create("ce")
    states, outs, values = [], [], []
    for x, y in arrays:
        batch = _batch(x, y)
        mod._step(batch)
        mod.update_metric(metric, batch.label)
        states.append(_state(mod))
        outs.append(mod.get_outputs()[0].asnumpy())
        values.append(metric.get()[1])
    return states, outs, values


def _fit(where, it, k, callback=None, metric="ce", monitor=None):
    mod = mx.mod.Module(_net(), context=_contexts(where))
    stepprof.reset()
    mod.fit(it, eval_metric=metric, optimizer="sgd", optimizer_params=SGD,
            arg_params=_params(), num_epoch=1, batch_end_callback=callback,
            batches_per_dispatch=k, monitor=monitor)
    return mod


def _close(got, want, what):
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=1e-4,
                                   atol=1e-5, err_msg="%s %s" % (what, name))


def _group_end(n, k, edges):
    """Steps dispatched when batch ``n``'s callback runs: the end of its
    group, where groups of ``k`` also end at each of ``edges``."""
    start = 0
    for edge in sorted(edges) + [float("inf")]:
        if n < edge:
            return int(min(start + ((n - start) // k + 1) * k, edge))
        start = edge


# 13 batches, not a multiple of 4, and the shape changes at batch 7: groups
# of K = 4 are 0-3, 4-6, 7-10, 11-12
CHANGE, TOTAL = 7, 13


def _mixed_arrays():
    return _arrays(CHANGE) + _arrays(TOTAL - CHANGE, rows=ROWS // 2, seed=1)


@pytest.mark.parametrize("where", [ONE, DP])
@pytest.mark.parametrize("k", [1, 4])
def test_fit_follows_the_hand_loop(where, k):
    """(a) parameters and metric values, (c) the state a callback finds and
    `locals["outs"]`, (d) how far the iterator has run, in one epoch with
    a short last group and one shape change."""
    arrays = _mixed_arrays()
    states, outs, values = _hand_loop(where, arrays)
    it = ListIter([_batch(x, y) for x, y in arrays])
    seen = []

    def callback(param):
        n = param.nbatch
        mod = param.locals["self"]
        done = _group_end(n, k, [CHANGE, TOTAL])
        # no step ran ahead: parameters and momenta are those after the
        # last step of batch n's own dispatch
        _close(_state(mod), states[done - 1], "at callback %d" % n)
        if k > 1 and param.locals["stacked"]:
            got = param.locals["outs"]["softmax_output"].asnumpy()
        else:
            got = mod.get_outputs()[0].asnumpy()
        np.testing.assert_allclose(got, outs[n], rtol=1e-4, atol=1e-5)
        assert param.eval_metric.get()[1] == pytest.approx(values[n],
                                                           rel=1e-4)
        # the iterator: one batch ahead of the callbacks, with K one group
        # and one batch ahead of the group's end
        ahead = 1 if k == 1 else k + 1
        assert it.handed <= min(done + ahead, TOTAL)
        seen.append(n)

    mod = _fit(where, it, k, callback)
    assert seen == list(range(TOTAL))
    _close(_state(mod), states[-1], "after fit")


@pytest.mark.parametrize("k, ring, total", [(1, 1, 11), (4, 10, 23)])
def test_a_batch_object_handed_out_again_is_transferred_again(k, ring, total):
    """(b) one `DataBatch` object (K = 4: a ring of 2K+2, since the loop
    holds two groups and a batch) whose arrays are swapped between calls:
    every step sees the new contents."""
    arrays = _arrays(total)
    states, _, _ = _hand_loop(ONE, arrays)
    mod = _fit(ONE, SwapIter(arrays, ring), k)
    _close(_state(mod), states[-1], "after fit")
    # and the mechanism was engaged, not bypassed
    assert sum(a.get("staged_ahead", 0) for r in stepprof.timeline()
               for n, _, _, a in r["spans"]) > 0


def test_staged_arrays_serve_one_use_and_never_stale_contents():
    mod = _module(ONE)
    (x0, y0), (x1, y1) = _arrays(2)
    batch = _batch(x0, y0)
    before = {n: a._data for n, a in mod._exec.arg_dict.items()}
    outputs = mod._exec.outputs
    mod.prepare(batch)
    # prepare touched nothing in the executor
    assert all(mod._exec.arg_dict[n]._data is v for n, v in before.items())
    assert mod._exec.outputs is outputs
    staged = batch._staged
    assert device_of(staged["data"][1]) == mx.tpu(1).jax_device()
    assert mod._load_batch_impl(batch) == (x0.nbytes + y0.nbytes,) * 2
    assert mod._exec.arg_dict["data"]._data is staged["data"][1]
    assert not hasattr(batch, "_staged")
    # the same object again: nothing is found, the batch is transferred
    assert mod._load_batch_impl(batch) == (x0.nbytes + y0.nbytes, 0)
    # arrays swapped after prepare: what was staged is not bound
    mod.prepare(batch)
    batch.data = [mx.nd.array(x1, ctx=mx.cpu())]
    assert mod._load_batch_impl(batch) == (x0.nbytes + y0.nbytes, y0.nbytes)
    np.testing.assert_array_equal(mod._exec.arg_dict["data"].asnumpy(), x1)


def test_prepare_leaves_alone_what_it_cannot_place():
    mod = _module(ONE)
    x, y = _arrays(1, rows=ROWS // 2)[0]
    short = _batch(x, y)
    mod.prepare(short)             # another shape than the bound one
    assert not hasattr(short, "_staged")
    there = mx.io.DataBatch(
        data=[mx.nd.array(x.repeat(2, 0), ctx=mx.tpu(1))],
        label=[mx.nd.array(y.repeat(2), ctx=mx.tpu(1))])
    mod.prepare(there)             # on the bound device already
    assert not hasattr(there, "_staged")
    host = mx.io.DataBatch(data=[x.repeat(2, 0)], label=[y.repeat(2)])
    mod.prepare(host)              # plain numpy is placed
    assert set(host._staged) == {"data", "softmax_label"}


def _spans(k):
    """The step records of one epoch over 9 batches of one shape."""
    arrays = _arrays(9)
    _fit(ONE, ListIter([_batch(x, y) for x, y in arrays]), k)
    return stepprof.timeline(), arrays[0][0].nbytes + arrays[0][1].nbytes


@pytest.mark.parametrize("k", [1, 4])
def test_h2d_spans_count_what_was_staged_ahead(k):
    """(e) `staged_ahead` beside `bytes` on the consumers' spans, `bytes`
    on `prepare`'s, and no phase nested in another."""
    steps, nbytes = _spans(k)
    batches = [r["batches"] for r in steps]
    assert batches == ([1] * 9 if k == 1 else [4, 4, 1])
    for i, rec in enumerate(steps):
        spans = sorted(rec["spans"], key=lambda sp: sp[1])
        for a, b in zip(spans, spans[1:]):
            assert a[1] + a[2] <= b[1], "%s overlaps %s" % (a[0], b[0])
        h2d = [a for n, _, _, a in rec["spans"] if n == "h2d"]
        consumed = [a for a in h2d if a.get("via") != "prepare"]
        prepared = [a for a in h2d if a.get("via") == "prepare"]
        assert [a["bytes"] for a in consumed] == [nbytes * rec["batches"]]
        # the epoch's first batch is staged by its own step; the first
        # group's members as the iterator hands them over
        assert consumed[0]["staged_ahead"] == \
            (0 if i == 0 and k == 1 else nbytes * rec["batches"])
        ahead = batches[i + 1] if i + 1 < len(steps) else 0
        if i == 0 and k > 1:
            ahead += batches[0]
        assert [a["bytes"] for a in prepared] == [nbytes] * ahead
    # every call of the iterator's `next` but the epoch's first, the one
    # that ended the epoch among them, has a `data_wait` of its own
    assert sum(n == "data_wait" for rec in steps
               for n, _, _, _ in rec["spans"]) == 9


def test_bucketing_module_stages_through_the_bucket():
    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(
            mx.sym.Reshape(data, shape=(-1, 4)), num_hidden=8, name="fc1")
        net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
        return mx.sym.SoftmaxOutput(net, name="softmax"), ("data",), \
            ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8,
                                 context=mx.tpu(1))
    mod.bind(data_shapes=[("data", (4, 8, 4))],
             label_shapes=[("softmax_label", (32,))])
    mod.init_params()
    mod.init_optimizer()
    rng = np.random.RandomState(0)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.randn(4, 6, 4), ctx=mx.cpu())],
        label=[mx.nd.array(rng.randint(0, 3, 24), ctx=mx.cpu())],
        bucket_key=6, provide_data=[("data", (4, 6, 4))],
        provide_label=[("softmax_label", (24,))])
    mod.prepare(batch)
    assert mod._curr_bucket_key == 8
    staged = batch._staged["data"][1]
    mod.forward_backward(batch)
    assert mod._curr_module._exec.arg_dict["data"]._data is staged


class TimedCE(mx.metric.CrossEntropy):
    """Cross-entropy that notes when each update was folded."""

    def __init__(self):
        super().__init__()
        self.at = []

    def update(self, labels, preds):
        self.at.append(time.perf_counter())
        super().update(labels, preds)


def _placed(rec, name):
    """[(start, end)] of ``rec``'s phases called ``name``, on perf_counter."""
    t0 = rec["clock"][1]
    return [(t0 + start, t0 + start + dur)
            for n, start, dur, _ in rec["spans"] if n == name]


NEVER, EVERY, MONITOR = "never read", "read every batch", "under a monitor"


@pytest.mark.parametrize("reads", [NEVER, EVERY, MONITOR])
@pytest.mark.parametrize("k", [1, 4])
def test_the_metric_is_read_one_dispatch_behind(k, reads, caplog):
    """With nothing reading the metric, batch n's update is queued in its
    own step and folded in the wait of the dispatch after, once that
    dispatch's compiled call has returned; a callback that reads every
    batch forces every fold and sees the hand loop's values; a monitor
    turns the lag off. One epoch, a short last group and a shape change;
    the epoch's logged value is the hand loop's in all three."""
    arrays = _mixed_arrays()
    _, _, values = _hand_loop(ONE, arrays)
    it = ListIter([_batch(x, y) for x, y in arrays])
    metric, seen = TimedCE(), []

    def callback(param):
        if reads == EVERY:
            assert param.eval_metric.get()[1] == pytest.approx(
                values[param.nbatch], rel=1e-4)
        seen.append(param.nbatch)

    monitor = mx.Monitor(100) if reads == MONITOR else None
    with caplog.at_level(logging.INFO):
        _fit(ONE, it, k, callback, metric, monitor)
    assert seen == list(range(TOTAL)) and len(metric.at) == TOTAL
    logged = [r.getMessage() for r in caplog.records
              if "Train-cross-entropy" in r.getMessage()]
    assert len(logged) == 1
    assert float(logged[0].split("=")[1]) == pytest.approx(values[-1],
                                                           rel=1e-4)
    assert metric._lag == 0 and not metric._pending

    steps = stepprof.timeline()
    sizes = [r["batches"] for r in steps]
    scan = k > 1 and monitor is None
    assert sizes == ([4, 3, 4, 2] if scan else [1] * TOTAL)
    first = 0    # number of the record's first batch
    for i, rec in enumerate(steps):
        waits = [a for n, _, _, a in rec["spans"]
                 if n == "device_compute" and a.get("via") == "update_metric"]
        assert len(waits) == rec["batches"]
        queued = sum(a["queued"] for a in waits)
        lagged = sum(a["lagged"] for a in waits)
        own = metric.at[first:first + rec["batches"]]
        (_, called), = _placed(rec, "dispatch") or [(None, None)]
        begun = _placed(rec, "device_compute")[0][0]
        end = rec["clock"][1] + rec["wall"]
        if reads == MONITOR:
            # folded at once, in the step's own wait, which keeps its
            # place behind the staging of the next batch
            assert (queued, lagged) == (0, 0)
            assert all(begun <= t <= end for t in own)
            staged = [start for n, start, _, a in rec["spans"]
                      if n == "h2d" and a.get("via") == "prepare"]
            assert len(staged) == (i + 1 < len(steps))
            assert all(rec["clock"][1] + start <= begun for start in staged)
        elif reads == EVERY:
            # forced by the callback, after the step's wait had ended
            assert (queued, lagged) == (rec["batches"], 0)
            assert all(called <= begun <= t <= end for t in own)
        else:
            assert queued == rec["batches"]
            assert lagged == (sizes[i - 1] if i else 0)
            # this step's own updates wait for the next dispatch's call
            # (the epoch's last: for the epoch's read of the metric)
            after = _placed(steps[i + 1], "dispatch")[0][1] \
                if i + 1 < len(steps) else end
            assert all(t >= after for t in own)
            # and those of the dispatch before were folded in this step's
            # waits, its own compiled call having returned
            if i:
                before = metric.at[first - sizes[i - 1]:first]
                assert all(called <= begun <= t <= end for t in before)
        first += rec["batches"]


def test_lagged_and_forced_folds_log_the_same_epoch_value(caplog):
    """The same floats in the same order: the epoch's line is the same text
    whether every fold lagged or every fold was forced."""
    lines = []
    for reads in (False, True):
        caplog.clear()
        arrays = _arrays(9)
        with caplog.at_level(logging.INFO):
            _fit(ONE, ListIter([_batch(x, y) for x, y in arrays]), 1,
                 (lambda p: p.eval_metric.get()) if reads else None)
        lines += [r.getMessage() for r in caplog.records
                  if "Train-cross-entropy" in r.getMessage()]
    assert len(lines) == 2 and lines[0] == lines[1]
