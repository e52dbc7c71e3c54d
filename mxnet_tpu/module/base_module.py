"""BaseModule: the training-loop contract.

Parity with reference `python/mxnet/module/base_module.py` (fit/score/
predict/iter_predict/forward_backward + get/set params). The fit loop is the
reference loop (`base_module.py:395-512`): per batch forward_backward →
update → update_metric, with epoch-end eval + checkpoints.
"""
from __future__ import annotations

import logging
import time

import numpy as np

from ..base import MXNetError
from .. import metric as metric_mod
from .. import io as io_mod
from .. import runprof
from .. import stepprof
from .. import telemetry
from ..ndarray import NDArray


class BatchEndParam:
    def __init__(self, epoch, nbatch, eval_metric, locals=None):
        self.epoch = epoch
        self.nbatch = nbatch
        self.eval_metric = eval_metric
        self.locals = locals


def _count_fit_batch(batch, eval_metric=None):
    """Per-batch throughput series: `callback.Speedometer` reads its
    samples/sec from these counters instead of recomputing locally.
    Every ``MXNET_RUNPROF_CHECK_EVERY``-th batch also sweeps the
    metric values through the training-health sentinels (`runprof`):
    a NaN/Inf loss trips ``run_anomalies_total`` + a flight-recorder
    dump instead of burning hours unnoticed."""
    try:
        samples = int(batch.data[0].shape[0])
    except Exception as exc:  # exotic batch payloads still count batches
        telemetry.swallowed("fit.count_batch", exc)
        samples = 0
    telemetry.counter("fit_batches_total",
                      help="train batches completed by Module.fit").inc()
    if samples:
        telemetry.counter("fit_samples_total",
                          help="train samples completed by Module.fit"
                          ).inc(samples)
    if eval_metric is not None and runprof.should_check():
        try:
            # one dispatch behind where `fit` defers the metric's updates:
            # the sentinels need a value, not the loop's wait for this
            # step; nothing while nothing is folded since the last reset
            runprof.observe_metrics(eval_metric._name_value_as_folded())
        except runprof.RunHealthError:
            raise   # MXNET_RUNPROF_HALT: a tripped sentinel stops fit
        except Exception as exc:  # a broken metric must not stop fit
            telemetry.swallowed("fit.health_check", exc)


def _as_list(obj):
    if obj is None:
        return []
    return obj if isinstance(obj, (list, tuple)) else [obj]


def _check_input_names(symbol, names, typename, throw):
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [arg for arg in args if not arg.endswith("_weight")
                      and not arg.endswith("_bias") and not arg.endswith("_gamma")
                      and not arg.endswith("_beta")]
        msg = "\033[91mYou created Module with Module(..., %s_names=%s) but " \
              "input with name '%s' is not found in symbol.list_arguments(). " \
              "Did you mean one of:\n\t%s\033[0m" % (
                  typename, str(names), name, "\n\t".join(candidates))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high level API --------------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def _step(self, data_batch):
        """One training step of the fit loop. Subclasses may override to
        fuse forward+backward+update into a single compiled dispatch."""
        self.forward_backward(data_batch)
        self.update()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0, sparse_row_id_fn=None):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                       eval_metric=eval_metric, locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(params)
            actual_num_batch += 1
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False, sparse_row_id_fn=None):
        assert self.binded and self.params_initialized
        if isinstance(eval_data, (NDArray, np.ndarray)):
            if isinstance(eval_data, np.ndarray):
                from ..ndarray import array
                eval_data = array(eval_data)
            # hand the NDArray straight to the iterator: its staging
            # path owns the (single) host conversion, so predict()'s
            # hot loop never forces a device->host sync itself
            eval_data = io_mod.NDArrayIter(eval_data,
                                           batch_size=eval_data.shape[0])
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy() for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                if len(out) != num_outputs:
                    raise ValueError("Cannot merge batches, as num of outputs is not the same "
                                     "in mini-batches. Maybe bucketing is used?")
            from ..ndarray import concatenate
            output_list2 = [concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, sparse_row_id_fn=None, batches_per_dispatch=1,
            scan_unroll=None, elastic=None, spmd=None):
        """Reference base_module.py:395 training loop.

        TPU extension: ``batches_per_dispatch=K`` groups K batches into ONE
        device dispatch (`Module._step_scan`: a lax.scan carries
        params/optimizer state through the K fused train steps). Metrics
        and batch callbacks still fire per batch, from the scan's stacked
        per-step outputs.

        Input placement runs one dispatch ahead, always: while step n runs
        `fit` fetches batch n+1 (the next group of K) and hands it to
        :meth:`prepare`, which `Module` answers by putting it on the
        device; the step then binds what it finds there (the group's
        members are stacked on the device, never on the host). No step
        runs ahead: when batch n's callbacks run, n+1 steps have been
        dispatched. The iterator runs one batch, with K one group and one
        batch, ahead of the callbacks.

        The metric is read one dispatch behind (`metric.py`): inside the
        loop batch n's `update_dict` is queued and folded into the sums
        after step n+1 has been handed to the device, so the loop waits
        for step n while the chip already has the next; a callback (or
        anything else) that reads the metric gets every queued update
        folded first and the value it would have got before. Not under a
        ``monitor``.

        SPMD extension: ``spmd=`` selects a `parallel.spmd` sharding
        policy (``"data_parallel"`` / ``"fsdp"`` / ``"tensor"``, a
        ``ShardingPolicy``, or an option dict) for the bind — parameters
        and optimizer state get real ``NamedSharding`` specs over the
        named mesh and the gradient sync runs inside the compiled step
        (see ``docs/architecture/sharding.md``).

        Elastic extension: ``elastic=`` (a checkpoint directory path, or a
        dict ``{"path": ..., "period": epochs, "keep_last": N}``) makes the
        run preemption-safe via `parallel/elastic.py`: parameters are
        checkpointed (sharded, commit-marked, rotated) every ``period``
        epochs, and a restarted run resumes from the latest complete
        checkpoint — ``begin_epoch`` fast-forwards past finished epochs."""
        assert num_epoch is not None, "please specify number of epochs"
        from ..initializer import Uniform
        if initializer is None:
            initializer = Uniform(0.01)

        bind_kwargs = {}
        if spmd is not None:
            # only Module-family binds accept spmd; passing it
            # unconditionally would break python_module subclasses
            bind_kwargs["spmd"] = spmd
        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind,
                  **bind_kwargs)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)

        if elastic is not None:
            from ..parallel import elastic as elastic_mod
            from .. import callback as callback_mod
            cfg = {"path": elastic} if isinstance(elastic, str) \
                else dict(elastic)
            known = {"path", "period", "keep_last", "backend",
                     "commit_timeout"}
            unknown = set(cfg) - known
            if unknown or "path" not in cfg:
                raise ValueError(
                    "fit(elastic=...) options are %s (got %s)"
                    % (sorted(known), sorted(cfg)))
            ckpt = elastic_mod.ElasticCheckpointer(
                cfg["path"], keep_last=cfg.get("keep_last", 3),
                backend=cfg.get("backend", "auto"),
                commit_timeout=cfg.get("commit_timeout"))
            resumed = elastic_mod.restore_module(ckpt, self)
            if resumed is not None:
                # run anatomy: price the epochs the previous incarnation
                # trained past this checkpoint (lost work on a restart).
                # Only on a REAL resume — a fresh run must not read a
                # previous run's leftover marker as phantom loss.
                runprof.note_resume(resumed, scope=ckpt.root)
                # checkpoint step == number of completed epochs
                begin_epoch = max(begin_epoch, resumed)
                self.logger.info("elastic: resumed from checkpoint; "
                                 "starting at epoch %d", begin_epoch)
            epoch_end_callback = list(_as_list(epoch_end_callback)) + [
                callback_mod.elastic_checkpoint(
                    ckpt, self, period=cfg.get("period", 1))]

        use_scan = batches_per_dispatch > 1 and monitor is None and \
            hasattr(self, "_step_scan")
        if scan_unroll is not None:
            # unroll factor for the K-step scan (see Module._step_scan)
            self.scan_unroll = int(scan_unroll)
        try:
            self._fit_loop(train_data, eval_data, eval_metric,
                           validation_metric, epoch_end_callback,
                           batch_end_callback, eval_end_callback,
                           eval_batch_end_callback, monitor,
                           sparse_row_id_fn, batches_per_dispatch,
                           use_scan, begin_epoch, num_epoch)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as exc:
            # crash flight recorder: leave the last N telemetry events +
            # compile/step metadata on disk before the traceback
            # unwinds, so post-mortems don't depend on scrollback
            from .. import xla_stats
            xla_stats.dump_flight_recorder(
                "fit_exception",
                error="%s: %s" % (type(exc).__name__, str(exc)[:400]))
            raise
        finally:
            eval_metric._defer(0)

    def _update_metric_waiting(self, eval_metric, data_batch):
        """`update_metric` in the loop's ``device_compute`` phase, with what
        the metric queued and folded behind a later dispatch written on it
        (the per-batch path's)."""
        with stepprof.phase("device_compute", via="update_metric") as wait:
            self.update_metric(eval_metric, data_batch.label)
            wait["queued"], wait["lagged"] = eval_metric._lag_counts()

    def _fit_loop(self, train_data, eval_data, eval_metric,
                  validation_metric, epoch_end_callback,
                  batch_end_callback, eval_end_callback,
                  eval_batch_end_callback, monitor, sparse_row_id_fn,
                  batches_per_dispatch, use_scan, begin_epoch, num_epoch):
        """The per-epoch body of :meth:`fit` (wrapped by the
        flight-recorder exception hook above)."""
        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            nbatch = 0
            data_iter = iter(train_data)
            end_of_batch = False
            next_data_batch = next(data_iter)
            # every loop iteration is one stepprof step, and the steps
            # tile the loop: the batch counters and the batch-end
            # callbacks run INSIDE the step in both paths, so one step
            # ends where the next begins (the `while` test between) and
            # a step's `other` is the loop's own Python, the throughput
            # counters and the user's callbacks. The taxonomy phases
            # inside come from _step/_step_scan/update (h2d, dispatch,
            # device_compute, sync, opt_update) plus the two loop-level
            # phases here: data_wait (iterator blocked) and
            # device_compute via the metric readback. That one is a
            # host wait: reading outputs to host is where the loop
            # waits for whatever of the step is still in flight (the
            # device's own time is the trace's, not this phase's).
            #
            # Input placement runs one dispatch ahead of the step that
            # consumes it, and only placement does: `prepare` puts batch
            # n+1 (scan path: the next group of K) on the device while
            # step n runs, between the dispatch and the read-back that
            # waits for it, in an h2d phase of its own beside data_wait.
            # Step n+1 is dispatched after batch n's callbacks, as ever.
            # The first batch of an epoch is staged by its step (the
            # first group's members as the iterator hands them over)
            #
            # The metric is read one dispatch behind: `update_dict` queues
            # batch n's labels and outputs and folds those of the dispatch
            # before (`EvalMetric._defer`: as many may stay queued as the
            # dispatch just issued has batches; none under a monitor).
            # So the loop's wait, in the device_compute phase, is for step
            # n-1 while step n is already on the device, and the compiled
            # call of step n+1 runs under step n. That wait is the loop's
            # back-pressure: the host is never more than one dispatch
            # ahead. It comes BEFORE the next batch (group) is fetched and
            # staged, so the device holds no more staged input than it did
            # when the wait was for step n (under a monitor nothing lags
            # and the read of step n keeps its place behind the staging).
            # Anything that reads the metric folds all of it first
            # (metric.py); an epoch's last dispatch is folded by the
            # epoch's own read below.
            group = None
            while use_scan and not (end_of_batch and group is None):
                with stepprof.step() as _sp:
                    if group is None:   # the epoch's first group
                        group, next_data_batch, end_of_batch = \
                            self._gather_group(
                                data_iter, next_data_batch,
                                batches_per_dispatch, sparse_row_id_fn)
                    _sp["batches"] = len(group)
                    # one dispatch for the group; a group of one and a
                    # module without a scan plan step batch by batch
                    stacked = self._step_scan(group) \
                        if len(group) > 1 else False
                    eval_metric._defer(len(group) if stacked else 1)
                    next_group = None
                    for k_i, b in enumerate(group):
                        if stacked is False:  # per-batch fallback
                            self._step(b)
                        with stepprof.phase("device_compute",
                                            via="update_metric") as _wait:
                            if stacked:
                                outs = {name: out[k_i]
                                        for name, out in
                                        zip(self.output_names, stacked)}
                                eval_metric.update_dict(
                                    dict(zip(self._label_names,
                                             b.label or [])),
                                    outs)
                            else:
                                self.update_metric(eval_metric, b.label)
                            _wait["queued"], _wait["lagged"] = \
                                eval_metric._lag_counts()
                        if k_i == 0 and not end_of_batch:
                            # the group's first fold has waited for the
                            # dispatch before: the next group travels
                            # under this one
                            next_group, next_data_batch, end_of_batch = \
                                self._gather_group(
                                    data_iter, next_data_batch,
                                    batches_per_dispatch, sparse_row_id_fn)
                        _count_fit_batch(b, eval_metric)
                        if batch_end_callback is not None:
                            batch_end_params = BatchEndParam(
                                epoch=epoch, nbatch=nbatch,
                                eval_metric=eval_metric,
                                locals=locals())
                            for callback in _as_list(batch_end_callback):
                                callback(batch_end_params)
                        nbatch += 1
                    group = next_group
            # one batch to a dispatch from here on
            eval_metric._defer(0 if monitor is not None else 1)
            while not end_of_batch:
                data_batch = next_data_batch
                with stepprof.step() as _sp:
                    if monitor is not None:
                        monitor.tic()
                        self.forward_backward(data_batch)
                        self.update()
                    else:
                        self._step(data_batch)
                    if monitor is None:
                        self._update_metric_waiting(eval_metric, data_batch)
                    with stepprof.phase("data_wait") as _dspan:
                        try:
                            next_data_batch = next(data_iter)
                        except StopIteration:
                            end_of_batch = True
                            _dspan["end_of_epoch"] = True
                    if not end_of_batch:
                        self.prepare(next_data_batch,
                                     sparse_row_id_fn=sparse_row_id_fn)
                    if monitor is not None:
                        # no lag: the read of this step's own outputs, with
                        # the next batch travelling under the step as ever
                        self._update_metric_waiting(eval_metric, data_batch)
                    _count_fit_batch(data_batch, eval_metric)
                    if monitor is not None:
                        monitor.toc_print()
                    if batch_end_callback is not None:
                        batch_end_params = BatchEndParam(
                            epoch=epoch, nbatch=nbatch,
                            eval_metric=eval_metric, locals=locals())
                        for callback in _as_list(batch_end_callback):
                            callback(batch_end_params)
                    nbatch += 1

            eval_metric._defer(0)
            for name, val in eval_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            toc = time.time()
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))

            arg_params_, aux_params_ = self.get_params()
            self.set_params(arg_params_, aux_params_)

            if epoch_end_callback is not None:
                for callback in _as_list(epoch_end_callback):
                    callback(epoch, self.symbol, arg_params_, aux_params_)

            if eval_data:
                res = self.score(eval_data, validation_metric,
                                 score_end_callback=eval_end_callback,
                                 batch_end_callback=eval_batch_end_callback,
                                 epoch=epoch)
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f", epoch, name, val)

            train_data.reset()

    def _gather_group(self, data_iter, first, size, sparse_row_id_fn):
        """The next group of the scan path: up to ``size`` batches of one
        shape, from ``first`` (fetched already) on. Returns ``(group, the
        batch fetched beyond it or None, whether the iterator has
        ended)``: a shape change (bucket edge) ends the group early, and
        so does the iterator. Each member is handed to :meth:`prepare` as
        it comes, so it travels to the device while the iterator fetches
        the next and, from the second group on, while the dispatch before
        runs; ``data_wait`` covers the iterator alone."""
        group, nb = [], first
        while True:
            group.append(nb)
            self.prepare(nb, sparse_row_id_fn=sparse_row_id_fn)
            with stepprof.phase("data_wait", gather="scan"):
                try:
                    nb = next(data_iter)
                except StopIteration:
                    return group, None, True
            if len(group) == size or \
                    nb.data[0].shape != group[0].data[0].shape:
                return group, nb, False

    # -- symbol/params ---------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v.as_in_context(v.context)
                     for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v.as_in_context(v.context)
                          for k, v in aux_params.items()})
        from ..ndarray import save
        save(fname, save_dict)

    def load_params(self, fname):
        from ..ndarray import load
        save_dict = load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return []

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized

    def install_monitor(self, mon):
        raise NotImplementedError()

    def prepare(self, data_batch, sparse_row_id_fn=None):
        pass

    # -- computation contract -------------------------------------------
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()
