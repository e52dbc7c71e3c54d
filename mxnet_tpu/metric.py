"""Evaluation metrics (reference `python/mxnet/metric.py`, 1,298 LoC).

Full registry: Accuracy, TopKAccuracy, F1, Perplexity, MAE, MSE, RMSE,
CrossEntropy, NegativeLogLikelihood, PearsonCorrelation, Loss, Torch, Caffe,
CustomMetric, CompositeEvalMetric, np/create helpers.

**What lags, and what forces a fold.** `update(labels, preds)` copies the
outputs to the host, which waits for the step that produces them. Inside
`Module.fit`'s training loop an `update_dict` therefore only QUEUES the
update, and the metric's own unchanged `update` folds it into
`sum_metric` / `num_inst` one dispatch later, when `fit` has handed the
next step to the device: the same numpy arithmetic on the same arrays in
the same order, so the value is the immediate sequence's bit for bit.
The queue holds the arrays and not their holders (a label `NDArray` an
iterator rebinds later does not change what is folded), and the outputs'
copy to the host starts when they are queued. Everywhere else
(`score`, `predict`, a hand loop, Gluon scripts) nothing is queued:
`update_dict` calls `update` at once, and so does `update` called
directly, always.

Every observation folds whatever is queued first: `get`,
`get_name_value`, `str()`, and any read or write of `sum_metric` /
`num_inst` (they are properties), which covers the `get` of `F1`,
`Perplexity`, `CompositeEvalMetric`, `CustomMetric` and of a user's
subclass that reads them. One reader is not an observation: `fit`'s own
health sweep (`runprof`, every 16th batch) takes the value as folded,
one dispatch behind, through `_name_value_as_folded`, forces nothing,
and passes while nothing has been folded since the last `reset`.
`reset` drops what is queued; its sums are zeroed either way. A
subclass that overrides `update` alone is deferred like the rest; one
that overrides `update_dict` without `super` is never deferred. A deferred `update` that raises does so at the fold, one
dispatch after the batch that caused it or at the next observation, with
a note that names the batch.
"""
from __future__ import annotations

import collections
import functools
import math

import numpy as _np

from .base import numeric_types, string_types
from .ndarray import NDArray

__all__ = ["EvalMetric", "CompositeEvalMetric", "Accuracy", "TopKAccuracy",
           "F1", "Perplexity", "MAE", "MSE", "RMSE", "CrossEntropy",
           "NegativeLogLikelihood", "PearsonCorrelation", "Loss",
           "CustomMetric", "np", "create"]

_METRIC_REGISTRY = {}


def register(klass, *names):
    for n in (names or (klass.__name__.lower(),)):
        _METRIC_REGISTRY[n.lower()] = klass
    return klass


def check_label_shapes(labels, preds, wrap=False, shape=False):
    if isinstance(labels, NDArray):
        labels = [labels]
    if isinstance(preds, NDArray):
        preds = [preds]
    if len(labels) != len(preds):
        raise ValueError("Shape of labels {} does not match shape of "
                         "predictions {}".format(len(labels), len(preds)))
    return labels, preds


def _held(arrays):
    """``arrays`` as they are now, for a fold that comes later: each dense
    `NDArray`'s buffer under a holder of its own (the iterator may rebind a
    recycled batch's label, an executor its outputs), its copy to the host
    started. Anything else (a sparse array) is held as it is."""
    held = []
    for arr in arrays:
        if type(arr) is NDArray:
            arr = NDArray(arr._data, arr._ctx)
            start = getattr(arr._data, "copy_to_host_async", None)
            if start is not None:
                start()
        held.append(arr)
    return held


@functools.lru_cache(maxsize=None)
def _picked_log_sum(eps, floor, ignore):
    """The jitted reduction of `_PickedLogSum`: (pred [..., classes], label)
    -> float32 [sum of -log(max(floor, p + eps)) over the rows that count,
    how many count], p the label's probability in a row."""
    import jax
    import jax.numpy as jnp

    def reduce(pred, label):
        label = label.reshape(-1).astype(jnp.int32)
        pred = pred.reshape(-1, pred.shape[-1])
        prob = jnp.take_along_axis(pred, label[:, None], axis=1)[:, 0]
        prob = prob.astype(jnp.float32) + jnp.float32(eps)
        counts = jnp.ones(label.shape, bool) if ignore is None \
            else label != ignore
        prob = jnp.where(counts, jnp.maximum(prob, jnp.float32(floor)), 1.0)
        return jnp.stack([-jnp.sum(jnp.log(prob)),
                          jnp.sum(counts).astype(jnp.float32)])

    return jax.jit(reduce)


class _PickedLogSum:
    """One batch's sum of -log p(label) and its count, reduced on the device
    that holds the outputs (the reference's `Perplexity` picks with
    `ndarray.pick` on the device too): two numbers cross to the host, not
    [rows, classes] probabilities. Made when the batch is seen, read when
    the metric folds it."""

    def __init__(self, label, pred, eps, floor, ignore):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        where = pred._data.sharding
        if isinstance(where, NamedSharding):
            where = NamedSharding(where.mesh, PartitionSpec())
        self._terms = _picked_log_sum(eps, floor, ignore)(
            pred._data, jax.device_put(label._data, where))
        self._terms.copy_to_host_async()

    @classmethod
    def of(cls, label, pred, eps=0.0, floor=0.0, ignore=None):
        """The reduction of a dense (label, pred) pair of `NDArray`s with
        one label a row; None for anything else, which the metric's numpy
        arithmetic takes."""
        if type(label) is not NDArray or type(pred) is not NDArray \
                or pred.ndim < 2 or label.size * pred.shape[-1] != pred.size:
            return None
        return cls(label, pred, eps, floor, ignore)

    def read(self):
        total, count = _np.asarray(self._terms)
        return float(total), int(count)


class EvalMetric:
    def __init__(self, name, output_names=None, label_names=None, **kwargs):
        self.name = str(name)
        self.output_names = output_names
        self.label_names = label_names
        self._kwargs = kwargs
        # the updates `update_dict` has queued, oldest first, as (number of
        # the batch, labels, preds); `_lag` of them may stay queued
        self._pending = collections.deque()
        self._lag = 0
        self._folding = False
        self._batch = self._queued = self._lagged = 0
        self.reset()

    def __str__(self):
        return "EvalMetric: {}".format(dict(self.get_name_value()))

    def get_config(self):
        config = self._kwargs.copy()
        config.update({"metric": self.__class__.__name__, "name": self.name,
                       "output_names": self.output_names,
                       "label_names": self.label_names})
        return config

    def update_dict(self, label, pred):
        if self.output_names is not None:
            pred = [pred[name] for name in self.output_names if name in pred]
        else:
            pred = list(pred.values())
        if self.label_names is not None:
            label = [label[name] for name in self.label_names if name in label]
        else:
            label = list(label.values())
        if not self._lag:
            self._fold()    # nothing, unless a loop left its last ones
            self.update(label, pred)
            return
        self._pending.append((self._batch, *self._hold(label, pred)))
        self._batch += 1
        self._queued += 1
        self._lagged += self._fold(keep=self._lag)

    def update(self, labels, preds):
        raise NotImplementedError()

    def _reduced(self, label, pred):
        """A device-side reduction of one (label, pred) pair that `update`
        can take in the pair's place (`_PickedLogSum`), or None: the pair
        itself, for the metric's numpy arithmetic."""
        return None

    def _terms(self, label, pred):
        """``pred`` where `_hold` has reduced the pair already, else its
        reduction now, or None."""
        return pred if isinstance(pred, _PickedLogSum) \
            else self._reduced(label, pred)

    def _hold(self, labels, preds):
        """(labels, preds) as `update_dict` queues them for a later fold
        through `update`: the arrays as they are now, their copy to the
        host started; a pair the metric reduces on the device as (None, its
        reduction), started here, right behind the step that made the
        outputs."""
        if len(labels) != len(preds):
            return _held(labels), _held(preds)
        kept_labels, kept_preds = [], []
        for label, pred in zip(labels, preds):
            terms = self._reduced(label, pred)
            kept_labels += [None] if terms is not None else _held([label])
            kept_preds += [terms] if terms is not None else _held([pred])
        return kept_labels, kept_preds

    def _defer(self, lag):
        """`Module.fit`'s: from now on `update_dict` queues its update and
        folds the oldest until at most ``lag`` stay queued (the batches of
        the dispatch `fit` has just issued); 0 is every other caller's:
        `update` at once."""
        if lag and not self._lag:
            self._batch = 0
        self._lag = lag

    def _fold(self, keep=0):
        """Folds the oldest queued updates through `update` until at most
        ``keep`` are left and returns how many that were. Does nothing
        while a fold runs: `update` itself reads and writes the sums."""
        if self._folding:
            return 0
        folded = 0
        while len(self._pending) > keep:
            batch, labels, preds = self._pending.popleft()
            self._folding = True
            try:
                self.update(labels, preds)
            except Exception as exc:
                exc.add_note(
                    "raised by the metric update of batch %d since Module.fit "
                    "began to defer them: update_dict queued it, and it is "
                    "folded one dispatch later or at the next read" % batch)
                raise
            finally:
                self._folding = False
            folded += 1
        return folded

    def _lag_counts(self):
        """(updates queued, queued updates folded behind a later one)
        since the last call: what `fit` writes on its
        ``device_compute via=update_metric`` phase. A fold that an
        observation forced counts in neither."""
        counts = self._queued, self._lagged
        self._queued = self._lagged = 0
        return counts

    def _name_value_as_folded(self):
        """`get_name_value` over the updates folded so far, the queued ones
        left queued: for `fit`'s health sweep alone, which can do with a
        value one dispatch behind and must not make the loop wait for the
        step it has just issued. No pairs while updates are queued and
        none has been folded since the last `reset`: there is no value
        yet, and `get` would call that NaN."""
        queued = self._pending
        if not queued:
            return self.get_name_value()
        if not self._num_inst:
            return []
        self._pending = collections.deque()
        try:
            return self.get_name_value()
        finally:
            self._pending = queued

    @property
    def sum_metric(self):
        self._fold()
        return self._sum_metric

    @sum_metric.setter
    def sum_metric(self, value):
        self._fold()
        self._sum_metric = value

    @property
    def num_inst(self):
        self._fold()
        return self._num_inst

    @num_inst.setter
    def num_inst(self, value):
        self._fold()
        self._num_inst = value

    def reset(self):
        self._pending.clear()
        self.num_inst = 0
        self.sum_metric = 0.0

    def get(self):
        if self.num_inst == 0:
            return (self.name, float("nan"))
        return (self.name, self.sum_metric / self.num_inst)

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name = [name]
        if not isinstance(value, list):
            value = [value]
        return list(zip(name, value))


@register
class CompositeEvalMetric(EvalMetric):
    def __init__(self, metrics=None, name="composite", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names)
        if metrics is None:
            metrics = []
        self.metrics = [create(i) for i in metrics]

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        try:
            return self.metrics[index]
        except IndexError:
            return ValueError("Metric index {} is out of range 0 and {}".format(
                index, len(self.metrics)))

    def update_dict(self, labels, preds):
        for metric in self.metrics:
            metric.update_dict(labels, preds)

    def update(self, labels, preds):
        for metric in self.metrics:
            metric.update(labels, preds)

    def _defer(self, lag):
        for metric in self.metrics:
            metric._defer(lag)

    def _lag_counts(self):
        """The children's counts, summed."""
        counts = [metric._lag_counts() for metric in self.metrics]
        return tuple(map(sum, zip(*counts))) or (0, 0)

    def _name_value_as_folded(self):
        return [pair for metric in self.metrics
                for pair in metric._name_value_as_folded()]

    def reset(self):
        try:
            for metric in self.metrics:
                metric.reset()
        except AttributeError:
            pass

    def get(self):
        names = []
        values = []
        for metric in self.metrics:
            name, value = metric.get()
            if isinstance(name, string_types):
                name = [name]
            if isinstance(value, numeric_types):
                value = [value]
            names.extend(name)
            values.extend(value)
        return (names, values)


@register
class Accuracy(EvalMetric):
    def __init__(self, axis=1, name="accuracy", output_names=None, label_names=None):
        super().__init__(name, axis=axis, output_names=output_names,
                         label_names=label_names)
        self.axis = axis

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            pred_np = pred_label.asnumpy()
            if pred_np.ndim > 1 and pred_np.shape[-1 if self.axis == -1 else self.axis] > 1 \
                    and pred_np.ndim != label.asnumpy().ndim:
                pred_np = _np.argmax(pred_np, axis=self.axis)
            label_np = label.asnumpy().astype("int32")
            pred_np = pred_np.astype("int32")
            if pred_np.shape != label_np.shape:
                pred_np = pred_np.reshape(label_np.shape)
            self.sum_metric += (pred_np.flat == label_np.flat).sum()
            self.num_inst += len(pred_np.flat)


@register
class TopKAccuracy(EvalMetric):
    def __init__(self, top_k=1, name="top_k_accuracy", output_names=None,
                 label_names=None):
        super().__init__(name, top_k=top_k, output_names=output_names,
                         label_names=label_names)
        self.top_k = top_k
        assert self.top_k > 1, "Please use Accuracy if top_k is no more than 1"
        self.name += "_%d" % self.top_k

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            assert len(pred_label.shape) <= 2, "Predictions should be no more than 2 dims"
            pred_np = _np.argsort(pred_label.asnumpy().astype("float32"), axis=1)
            label_np = label.asnumpy().astype("int32")
            num_samples = pred_np.shape[0]
            num_dims = len(pred_np.shape)
            if num_dims == 1:
                self.sum_metric += (pred_np.flat == label_np.flat).sum()
            elif num_dims == 2:
                num_classes = pred_np.shape[1]
                top_k = min(num_classes, self.top_k)
                for j in range(top_k):
                    self.sum_metric += (pred_np[:, num_classes - 1 - j].flat ==
                                        label_np.flat).sum()
            self.num_inst += num_samples


@register
class F1(EvalMetric):
    def __init__(self, name="f1", output_names=None, label_names=None, average="macro"):
        self.average = average
        super().__init__(name=name, output_names=output_names, label_names=label_names)
        self.reset()

    def reset(self):
        super().reset()
        self.tp = self.fp = self.fn = 0.0

    @staticmethod
    def _f1(tp, fp, fn):
        prec = tp / max(tp + fp, 1e-12)
        rec = tp / max(tp + fn, 1e-12)
        return 2 * prec * rec / max(prec + rec, 1e-12)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            pred_np = pred.asnumpy()
            label_np = label.asnumpy().astype("int32")
            if pred_np.ndim > 1:
                pred_np = _np.argmax(pred_np, axis=1)
            pred_np = pred_np.astype("int32").reshape(-1)
            label_np = label_np.reshape(-1)
            tp = ((pred_np == 1) & (label_np == 1)).sum()
            fp = ((pred_np == 1) & (label_np == 0)).sum()
            fn = ((pred_np == 0) & (label_np == 1)).sum()
            # 'macro' averages the per-update F1; 'micro' pools the counts
            # (reference metric.py F1.update_binary_stats semantics)
            self.tp += tp
            self.fp += fp
            self.fn += fn
            self.sum_metric += self._f1(tp, fp, fn)
            self.num_inst += 1

    def get(self):
        if self.num_inst == 0:
            return (self.name, float("nan"))
        if self.average == "macro":
            return (self.name, self.sum_metric / self.num_inst)
        return (self.name, self._f1(self.tp, self.fp, self.fn))


@register
class Perplexity(EvalMetric):
    def __init__(self, ignore_label=None, axis=-1, name="perplexity",
                 output_names=None, label_names=None):
        super().__init__(name, ignore_label=ignore_label,
                         output_names=output_names, label_names=label_names)
        self.ignore_label = ignore_label
        self.axis = axis

    def _reduced(self, label, pred):
        return _PickedLogSum.of(label, pred, floor=1e-10,
                                ignore=self.ignore_label)

    def update(self, labels, preds):
        assert len(labels) == len(preds)
        loss = 0.0
        num = 0
        for label, pred in zip(labels, preds):
            terms = self._terms(label, pred)
            if terms is not None:
                total, count = terms.read()
                loss += total
                num += count
                continue
            label_np = label.asnumpy().astype("int32").reshape(-1)
            pred_np = pred.asnumpy()
            pred_np = pred_np.reshape(-1, pred_np.shape[-1])
            probs = pred_np[_np.arange(label_np.shape[0]), label_np]
            if self.ignore_label is not None:
                ignore = (label_np == self.ignore_label)
                probs = _np.where(ignore, 1.0, probs)
                num -= ignore.sum()
            loss -= _np.sum(_np.log(_np.maximum(1e-10, probs)))
            num += label_np.shape[0]
        self.sum_metric += loss
        self.num_inst += num

    def get(self):
        if self.num_inst == 0:
            return (self.name, float("nan"))
        return (self.name, math.exp(self.sum_metric / self.num_inst))


@register
class MAE(EvalMetric):
    def __init__(self, name="mae", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names, label_names=label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label_np = label.asnumpy()
            pred_np = pred.asnumpy()
            if len(label_np.shape) == 1:
                label_np = label_np.reshape(label_np.shape[0], 1)
            if len(pred_np.shape) == 1:
                pred_np = pred_np.reshape(pred_np.shape[0], 1)
            self.sum_metric += _np.abs(label_np - pred_np).mean()
            self.num_inst += 1


@register
class MSE(EvalMetric):
    def __init__(self, name="mse", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names, label_names=label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label_np = label.asnumpy()
            pred_np = pred.asnumpy()
            if len(label_np.shape) == 1:
                label_np = label_np.reshape(label_np.shape[0], 1)
            if len(pred_np.shape) == 1:
                pred_np = pred_np.reshape(pred_np.shape[0], 1)
            self.sum_metric += ((label_np - pred_np) ** 2.0).mean()
            self.num_inst += 1


@register
class RMSE(EvalMetric):
    def __init__(self, name="rmse", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names, label_names=label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label_np = label.asnumpy()
            pred_np = pred.asnumpy()
            if len(label_np.shape) == 1:
                label_np = label_np.reshape(label_np.shape[0], 1)
            if len(pred_np.shape) == 1:
                pred_np = pred_np.reshape(pred_np.shape[0], 1)
            self.sum_metric += _np.sqrt(((label_np - pred_np) ** 2.0).mean())
            self.num_inst += 1


@register
class CrossEntropy(EvalMetric):
    def __init__(self, eps=1e-12, name="cross-entropy", output_names=None,
                 label_names=None):
        super().__init__(name, eps=eps, output_names=output_names,
                         label_names=label_names)
        self.eps = eps

    def _reduced(self, label, pred):
        if pred.ndim != 2:
            return None
        return _PickedLogSum.of(label, pred, eps=self.eps)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            terms = self._terms(label, pred)
            if terms is not None:
                total, count = terms.read()
                self.sum_metric += total
                self.num_inst += count
                continue
            label_np = label.asnumpy()
            pred_np = pred.asnumpy()
            label_np = label_np.ravel()
            assert label_np.shape[0] == pred_np.shape[0]
            prob = pred_np[_np.arange(label_np.shape[0]), _np.int64(label_np)]
            self.sum_metric += (-_np.log(prob + self.eps)).sum()
            self.num_inst += label_np.shape[0]


@register
class NegativeLogLikelihood(EvalMetric):
    def __init__(self, eps=1e-12, name="nll-loss", output_names=None,
                 label_names=None):
        super().__init__(name, eps=eps, output_names=output_names,
                         label_names=label_names)
        self.eps = eps

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label_np = label.asnumpy()
            pred_np = pred.asnumpy()
            label_np = label_np.ravel()
            num_examples = pred_np.shape[0]
            assert label_np.shape[0] == num_examples
            prob = pred_np[_np.arange(num_examples, dtype=_np.int64),
                           _np.int64(label_np)]
            self.sum_metric += (-_np.log(prob + self.eps)).sum()
            self.num_inst += num_examples


@register
class PearsonCorrelation(EvalMetric):
    def __init__(self, name="pearsonr", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names, label_names=label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label_np = label.asnumpy()
            pred_np = pred.asnumpy()
            self.sum_metric += _np.corrcoef(pred_np.ravel(), label_np.ravel())[0, 1]
            self.num_inst += 1


@register
class Loss(EvalMetric):
    def __init__(self, name="loss", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names, label_names=label_names)

    def update(self, _, preds):
        if isinstance(preds, NDArray):
            preds = [preds]
        for pred in preds:
            loss = pred.asnumpy().sum()
            self.sum_metric += loss
            self.num_inst += pred.size


@register
class CustomMetric(EvalMetric):
    def __init__(self, feval, name=None, allow_extra_outputs=False,
                 output_names=None, label_names=None):
        if name is None:
            name = feval.__name__
            if name.find("<") != -1:
                name = "custom(%s)" % name
        super().__init__(name, feval=feval, allow_extra_outputs=allow_extra_outputs,
                         output_names=output_names, label_names=label_names)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            labels, preds = check_label_shapes(labels, preds)
        for pred, label in zip(preds, labels):
            label = label.asnumpy()
            pred = pred.asnumpy()
            reval = self._feval(label, pred)
            if isinstance(reval, tuple):
                (sum_metric, num_inst) = reval
                self.sum_metric += sum_metric
                self.num_inst += num_inst
            else:
                self.sum_metric += reval
                self.num_inst += 1


def np(numpy_feval, name=None, allow_extra_outputs=False):
    def feval(label, pred):
        return numpy_feval(label, pred)
    feval.__name__ = numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


def create(metric, *args, **kwargs):
    if callable(metric):
        return CustomMetric(metric, *args, **kwargs)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        composite_metric = CompositeEvalMetric()
        for child_metric in metric:
            composite_metric.add(create(child_metric, *args, **kwargs))
        return composite_metric
    if isinstance(metric, str):
        try:
            return _METRIC_REGISTRY[metric.lower()](*args, **kwargs)
        except KeyError:
            raise ValueError("Metric must be either callable or in registry: %s"
                             % metric) from None
    raise TypeError("metric should be string, callable, list or EvalMetric")


# register common aliases (reference registers 'acc', 'ce', 'nll_loss')
_METRIC_REGISTRY["acc"] = Accuracy
_METRIC_REGISTRY["ce"] = CrossEntropy
_METRIC_REGISTRY["nll_loss"] = NegativeLogLikelihood
_METRIC_REGISTRY["top_k_accuracy"] = TopKAccuracy
_METRIC_REGISTRY["top_k_acc"] = TopKAccuracy
_METRIC_REGISTRY["pearsonr"] = PearsonCorrelation
