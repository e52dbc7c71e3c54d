"""Module: symbolic training on one executor (optionally mesh-sharded).

Parity with reference `python/mxnet/module/module.py` (bind/init_params/
init_optimizer/forward/backward/update/...). TPU-native differences:

- The reference's DataParallelExecutorGroup (one executor per GPU, batch
  sliced on the host, grads reduced via KVStore comm) is replaced by ONE
  executor whose jitted program runs SPMD over all chips when the module's
  context list has >1 device: inputs are placed batch-sharded over a 'dp'
  mesh, parameters replicated, and XLA inserts the gradient psum over ICI.
- update() goes through the KVStore API exactly like the reference
  (`_update_params_on_kvstore`), so user code and custom updaters port 1:1.
"""
from __future__ import annotations

import logging
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError, device_of
from ..context import Context, cpu
from ..executor import Executor
from ..initializer import Uniform, InitDesc
from ..ndarray import NDArray, zeros as nd_zeros
from .. import optimizer as opt
from .. import kvstore as kvs
from .. import stepprof
from .base_module import BaseModule, _check_input_names


def _create_kvstore(kvstore, num_device, arg_params):
    """Reference `python/mxnet/model.py:_create_kvstore`."""
    update_on_kvstore = True
    if kvstore is None:
        kv = None
    elif isinstance(kvstore, kvs.KVStore):
        kv = kvstore
    elif isinstance(kvstore, str):
        if num_device == 1 and "dist" not in kvstore:
            kv = None
        else:
            kv = kvs.create(kvstore)
            if kvstore == "local":
                max_size = max(np.prod(param.shape) for param in arg_params.values())
                if max_size > 1024 * 1024 * 16:
                    update_on_kvstore = False
    else:
        raise TypeError("kvstore must be KVStore, str or None")
    if kv is None:
        update_on_kvstore = False
    return (kv, update_on_kvstore)


class Module(BaseModule):
    _fused = None  # fused optimizer applier, resolved at first update

    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None, group2ctxs=None,
                 compression_params=None):
        super().__init__(logger=logger)
        if context is None:
            context = cpu()
        if isinstance(context, Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list
        self._group2ctxs = group2ctxs
        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        state_names = list(state_names) if state_names is not None else []
        fixed_param_names = list(fixed_param_names) if fixed_param_names is not None else []
        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, state_names, "state", True)
        _check_input_names(symbol, fixed_param_names, "fixed_param", True)
        arg_names = symbol.list_arguments()
        input_names = data_names + label_names + state_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = fixed_param_names
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = state_names
        self._output_names = symbol.list_outputs()
        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._compression_params = compression_params
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._exec = None
        self._data_shapes = None
        self._label_shapes = None
        self._grad_req = None
        self._monitor = None
        self._fused_plan = None
        self._scan_plans = None
        self._spmd = None  # ShardingPolicy once bound over a mesh
        self._spmd_explicit = False  # spmd=.../MXNET_SPMD opt-in (donation)
        self._spmd_infer = None  # out-shapes cache from the placement map

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        from ..model import load_checkpoint
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        from ..model import save_checkpoint
        self._sync_params_from_devices()
        save_checkpoint(prefix, epoch, self.symbol, *self.get_params())
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)

    # -- properties ------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return list(zip(self._output_names, self._out_shapes))

    # -- params ----------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and force_init=False. "
                          "init_params call ignored.", stacklevel=2)
            return
        assert self.binded, "call bind before initializing the parameters"

        def _impl(name, arr, cache):
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        if cache_arr.shape != arr.shape:
                            raise MXNetError("shape mismatch for %s: %s vs %s"
                                             % (name, cache_arr.shape, arr.shape))
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    if initializer is not None:
                        initializer(InitDesc(name, attrs={}), arr)
            else:
                if initializer is not None:
                    initializer(InitDesc(name, attrs={}), arr)

        attrs = self._symbol.attr_dict()
        for name in self._param_names:
            arr = self._exec.arg_dict[name]
            desc_attrs = attrs.get(name, {})
            if initializer is not None and "__init__" in desc_attrs and \
                    (arg_params is None or name not in arg_params):
                initializer(InitDesc(name, attrs=desc_attrs), arr)
            else:
                _impl(name, arr, arg_params)
        for name in self._aux_names:
            arr = self._exec.aux_dict[name]
            _impl(name, arr, aux_params)

        self.params_initialized = True
        self._params_dirty = True
        self._sync_params_from_devices()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params, allow_missing=allow_missing,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and force_init=False. "
                          "set_params call ignored.", stacklevel=2)
            return
        for name, arr in (arg_params or {}).items():
            if name in self._exec.arg_dict:
                self._exec.arg_dict[name][:] = arr
        for name, arr in (aux_params or {}).items():
            if name in self._exec.aux_dict:
                self._exec.aux_dict[name][:] = arr
        self.params_initialized = True
        self._params_dirty = True

    def _sync_params_from_devices(self):
        if not self.binded:
            return
        self._arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
        self._aux_params = {n: self._exec.aux_dict[n] for n in self._aux_names}
        self._params_dirty = False

    # -- bind ------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write", type_dict=None, spmd=None):
        """``type_dict`` (TPU extension): per-argument dtype overrides, e.g.
        ``{'data': 'bfloat16', **{p: 'bfloat16' for p in param_names}}`` for
        MXU-native bf16 training; aux states (BN moving stats) keep f32
        unless named explicitly. The reference reaches the same state via
        per-var __dtype__ attrs + infer_type.

        ``spmd`` (TPU extension): a `parallel.spmd` sharding policy —
        ``"data_parallel"`` / ``"fsdp"`` / ``"tensor"``, a
        ``ShardingPolicy``, or an option dict — selecting how parameters
        and the batch are laid out over the named mesh. With a
        multi-device ``context`` list the mesh spans those devices;
        with a single (default) context it spans every local device
        (or the ``devices`` an option dict names). An explicit ``spmd``
        also lets the fused step write the new params and gradients over
        the old buffers (donation); on a mesh of one device that is all
        it does. Multi-device contexts without ``spmd`` keep the
        historical replicated data-parallel layout (overridable via
        ``MXNET_SPMD``)."""
        if force_rebind:
            self._exec = None
            self.binded = False
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        assert not (not for_training and inputs_need_grad)

        self._data_shapes = _norm_shapes(data_shapes)
        self._label_shapes = _norm_shapes(label_shapes) if label_shapes else []
        shapes = {}
        for desc in self._data_shapes + self._label_shapes:
            shapes[desc[0]] = desc[1]

        req = {}
        for name in self._symbol.list_arguments():
            if not for_training:
                req[name] = "null"
            elif name in self._param_names:
                req[name] = "null" if name in self._fixed_param_names else grad_req
            elif name in [d[0] for d in self._data_shapes]:
                req[name] = grad_req if inputs_need_grad else "null"
            else:
                req[name] = "null"
        self._grad_req = req

        shared_exec = shared_module._exec if shared_module is not None else None
        self._fused_plan = None
        self._scan_plans = None
        ctx = self._context[0]
        shardings = self._spmd_shardings(shapes, spmd, type_dict)
        # group2ctxs: reference accepts a dict or a per-dp-replica list of
        # dicts (executor_group.py); the SPMD dp path replaces per-replica
        # executors, so one group map applies
        g2c = self._group2ctxs
        if isinstance(g2c, (list, tuple)):
            g2c = g2c[0] if g2c else None
        if g2c and len(self._context) > 1:
            from ..base import MXNetError
            raise MXNetError(
                "group2ctxs with a multi-device data-parallel context "
                "list is not supported: use ONE group2ctx dict (model "
                "parallel) or context=[...] (data parallel), not both")
        self._exec = Executor.simple_bind(self._symbol, ctx, grad_req=req,
                                          shared_exec=shared_exec,
                                          shardings=shardings,
                                          group2ctx=g2c,
                                          type_dict=type_dict, **shapes)
        # memory ledger: what this module pinned in device memory —
        # PER-DEVICE shard bytes (== global bytes when replicated or
        # single-device), so memory_report() and serving admission
        # control see the HBM a device actually holds under FSDP
        from .. import xla_stats
        scope = self._ledger_scope()
        xla_stats.ledger_set(scope, "params", xla_stats.tree_shard_bytes(
            [self._exec.arg_dict[n] for n in self._param_names
             if n in self._exec.arg_dict]))
        xla_stats.ledger_set(scope, "grads", xla_stats.tree_shard_bytes(
            [g for g in self._exec.grad_dict.values() if g is not None]))
        xla_stats.ledger_set(scope, "aux", xla_stats.tree_shard_bytes(
            list(self._exec.aux_dict.values())))
        self._opt_bytes_noted = False
        if getattr(self, "_spmd_infer", None) is not None:
            self._out_shapes = self._spmd_infer  # inferred with the map
        else:
            from ..symbol.symbol import _graph_infer
            _, self._out_shapes, _ = _graph_infer(self._symbol, shapes)
        self.binded = True
        # restore previously held params (e.g. after Module.load)
        if self._arg_params is not None:
            for name, arr in self._arg_params.items():
                if name in self._exec.arg_dict and \
                        self._exec.arg_dict[name] is not arr:
                    arr.copyto(self._exec.arg_dict[name])
        if self._aux_params is not None:
            for name, arr in self._aux_params.items():
                if name in self._exec.aux_dict and \
                        self._exec.aux_dict[name] is not arr:
                    arr.copyto(self._exec.aux_dict[name])
        if shared_module is not None and shared_module.params_initialized:
            self.params_initialized = True
            self._sync_params_from_devices()

    def _ledger_scope(self):
        """Memory-ledger owner label for this module: the symbol's head
        name when it has one, else the class name."""
        name = None
        try:
            name = self._symbol.name
        except Exception as exc:  # headless symbol: class name fallback
            from .. import telemetry
            telemetry.swallowed("module.ledger_scope", exc)
        return name or type(self).__name__.lower()

    def _note_optimizer_bytes(self, state_arrays):
        """One-time optimizer-state byte accounting (first update):
        per-device shard bytes — under FSDP the optimizer state inherits
        the parameter sharding, and the ledger must record what one
        device holds, not the global figure."""
        if getattr(self, "_opt_bytes_noted", False):
            return
        from .. import xla_stats
        xla_stats.ledger_set(self._ledger_scope(), "optimizer",
                             xla_stats.tree_shard_bytes(state_arrays))
        self._opt_bytes_noted = True

    def _spmd_shardings(self, shapes, spmd, type_dict=None):
        """Placement map for SPMD training: ONE executor whose buffers
        live on a named mesh — inputs sharded along 'data', parameters
        laid out by the selected `parallel.spmd.ShardingPolicy`
        (replicated / fsdp-sharded / tensor-sharded); gradients and
        optimizer state inherit the parameter placement, so XLA issues
        the gradient all-reduce (or reduce-scatter) INSIDE the compiled
        step. The reference instead runs one executor per device and
        reduces grads through the KVStore
        (executor_group.py:129,289,330); the in-program collective
        subsumes that reduction and overlaps it with backward.

        Policy selection: the ``spmd`` bind argument; else ``MXNET_SPMD``
        for multi-device contexts; else plain replicated data parallelism
        for multi-device contexts; else None (single-device executor)."""
        from ..parallel import spmd as spmd_mod
        # explicit selection (the spmd= argument or MXNET_SPMD) unlocks
        # the policy extras — notably param-buffer donation; the implicit
        # multi-device default keeps the legacy data-parallel guarantees
        # (params NOT donated: user code may hold views)
        explicit = spmd is not None
        if spmd is None:
            try:
                spmd = spmd_mod.default_policy_name() \
                    if len(self._context) > 1 else None
            except ValueError as e:  # bad MXNET_SPMD value
                raise MXNetError(str(e))
            explicit = spmd is not None
            if spmd is None and len(self._context) > 1:
                spmd = "data_parallel"
        if spmd is None:
            self._spmd = None
            self._spmd_explicit = False
            self._spmd_infer = None
            return None
        self._spmd_explicit = explicit
        if len(self._context) > 1:
            devices = [c.jax_device() for c in self._context]
        else:
            import jax
            devices = list(jax.devices())  # spmd over all local devices
        try:
            policy = spmd_mod.resolve(spmd, devices=devices)
        except (TypeError, ValueError) as e:  # bad policy / devices
            raise MXNetError(str(e))
        if policy.mesh.size == 1:
            # a mesh of one device (the only local one, or the one an
            # option dict's ``devices`` names): nothing to lay out, so the
            # single-device executor it is (a mesh of one would give the
            # same buffers a second name, `NamedSharding` beside
            # `SingleDeviceSharding`, and every program that sees both a
            # retrace). The explicit choice keeps what it unlocks: the step
            # writes over its params in place
            self._spmd = None
            self._spmd_infer = None
            return None
        self._spmd = policy
        from ..symbol.symbol import _graph_infer
        arg_shapes_d, out_shapes, _ = _graph_infer(
            self._symbol, shapes, type_dict=type_dict)
        self._spmd_infer = out_shapes  # reused by bind: one inference
        input_names = set(self._data_names) | set(self._label_names) \
            | set(self._state_names)
        arg_shapes = {}
        for name in self._symbol.list_arguments():
            shape = shapes.get(name, arg_shapes_d.get(name))
            if shape is None:
                raise MXNetError("cannot infer shape of argument %s for "
                                 "spmd placement" % name)
            arg_shapes[name] = tuple(shape)
        try:
            return policy.shardings_for(arg_shapes, input_names,
                                        aux_names=self._aux_names)
        except ValueError as e:  # indivisible batch dim
            raise MXNetError(str(e))

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._data_shapes = _norm_shapes(data_shapes)
        self._label_shapes = _norm_shapes(label_shapes) if label_shapes else []
        shapes = {}
        for desc in self._data_shapes + self._label_shapes:
            shapes[desc[0]] = desc[1]
        self._exec = self._exec.reshape(**shapes)
        self._fused_plan = None
        self._scan_plans = None

    # -- optimizer -------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        if self._params_dirty:
            self._sync_params_from_devices()
        self._fused = None  # re-resolve the fused applier per optimizer
        self._fused_plan = None
        self._scan_plans = None
        # SPMD multi-device modules reduce gradients in-program (psum over
        # the dp mesh), so the reference's local-kvstore grad reduction
        # (model.py:_create_kvstore num_device>1) is already done: treat as
        # one logical device. Explicit dist kvstores still apply on top.
        eff_devices = 1 if self._exec._shardings is not None \
            else len(self._context)
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, eff_devices, {n: self._exec.arg_dict[n]
                                   for n in self._param_names})
        batch_size = self._data_shapes[0][1][0]
        if kvstore and "dist" in kvstore.type and "_async" not in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        idx2name = {i: n for i, n in enumerate(self._param_names)}
        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                warnings.warn("Optimizer created manually outside Module but "
                              "rescale_grad is not normalized to 1.0/batch_size/num_workers. "
                              "Is this intended?", stacklevel=2)
            if not optimizer.idx2name:
                optimizer.param_dict = {}
                optimizer.idx2name = idx2name.copy()

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            for i, name in enumerate(self._param_names):
                # kv.init broadcasts rank 0's value and writes it back
                # into the passed array (kvstore.py), so all workers
                # start from identical params
                kvstore.init(i, self._exec.arg_dict[name])
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
        if not update_on_kvstore:
            self._updater = opt.get_updater(optimizer)

        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def borrow_optimizer(self, shared_module):
        """Share optimizer/kvstore/updater with another module (bucketing)."""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self._fused = None  # re-resolve against the borrowed updater
        self._fused_plan = None
        self._scan_plans = None
        self.optimizer_initialized = True

    # -- compute ---------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        self._load_batch(data_batch)
        self._exec.forward(is_train=is_train)

    def _load_batch(self, data_batch):
        # the h2d phase is TRAINING-step anatomy: only record it inside
        # an open step record, so predict/score staging does not pollute
        # the step_h2d_seconds histogram (and .prom-derived verdicts)
        if stepprof.in_step():
            with stepprof.phase("h2d") as ph:
                ph["bytes"], ph["staged_ahead"] = \
                    self._load_batch_impl(data_batch)
        else:
            self._load_batch_impl(data_batch)

    def _load_batch_impl(self, data_batch):
        """Bind the batch to the executor's inputs: what :meth:`prepare`
        put on the device ahead is bound as it is (and leaves the batch:
        one use), the rest is placed now. Returns the bytes bound in all
        and those of them found ready, the ``bytes`` and ``staged_ahead``
        of the ``h2d`` phase."""
        data = data_batch.data
        if any(self._exec.arg_dict[n].shape != a.shape
               for n, a in zip(self._data_names, data)):
            # dynamic batch (bucketing/last small batch): rebind via reshape
            self.reshape([(n, a.shape) for n, a in zip(self._data_names, data)],
                         [(n, a.shape) for n, a in
                          zip(self._label_names, data_batch.label or [])] or None)
        ahead = _take_staged(data_batch)
        bound = found = 0
        for name, arr in self._batch_inputs(data_batch):
            dst = self._exec.arg_dict[name]
            ready = _staged_for(ahead, name, arr)
            if ready is None:
                dst[:] = arr
            else:
                dst._data = ready
                found += ready.nbytes
            bound += dst._data.nbytes
        return bound, found

    def _batch_inputs(self, data_batch):
        """[(name, the batch's array)] for every data and label name the
        executor has a slot for."""
        pairs = list(zip(self._data_names, data_batch.data))
        for name, arr in zip(self._label_names, data_batch.label or []):
            if name in self._exec.arg_dict:
                pairs.append((name, arr))
        return pairs

    def _input_placement(self, name):
        """Where the executor takes input ``name``: its ``NamedSharding``
        under ``context=[several]`` / ``spmd=``, else the bound device."""
        shardings = self._exec._shardings
        if shardings is not None and name in shardings:
            return shardings[name]
        return device_of(self._exec.arg_dict[name]._data)

    def _place_input(self, name, arr):
        """``arr`` as the executor's slot ``name`` takes it: the slot's
        dtype, on its device or sharding, by one asynchronous transfer (an
        array that is there already comes back as it is)."""
        dtype = self._exec.arg_dict[name].dtype
        if isinstance(arr, NDArray):
            val = arr._data if arr.dtype == dtype else arr._data.astype(dtype)
        else:
            val = np.asarray(arr, dtype=dtype)
        return jax.device_put(val, self._input_placement(name))

    def prepare(self, data_batch, sparse_row_id_fn=None):
        """Put ``data_batch`` on the device one dispatch ahead of the step
        that consumes it (reference module.py ``prepare``; `fit` calls it
        on batch n+1 while step n runs). For every bound data and label
        name it issues the transfer :meth:`_load_batch_impl` /
        :meth:`stack_batches` would issue later and returns at once; the
        device arrays travel with THIS use of the batch (``_staged``, which
        the consumer takes off it), each beside the array it was made
        from, so a batch object handed out again is transferred again.
        Left to the consumer as before: arrays that live on the bound
        placement already, sparse arrays, and a batch whose shape is not
        the bound one (bucket edge, short last batch). Nothing in the
        executor is touched."""
        assert self.binded
        if stepprof.in_step():
            with stepprof.phase("h2d", via="prepare") as ph:
                ph["bytes"] = self._stage_ahead(data_batch)
        else:
            self._stage_ahead(data_batch)

    def _stage_ahead(self, data_batch):
        arg_dict = self._exec.arg_dict
        _take_staged(data_batch)   # an earlier call's, never consumed
        inputs = self._batch_inputs(data_batch)
        if any(arr.shape != arg_dict[name].shape for name, arr in inputs):
            return 0
        staged, nbytes = {}, 0
        for name, arr in inputs:
            if isinstance(arr, NDArray):
                if arr.stype != "default" or \
                        device_of(arr._data) == self._input_placement(name):
                    continue
            elif not isinstance(arr, np.ndarray):
                continue
            staged[name] = (arr, self._place_input(name, arr))
            nbytes += staged[name][1].nbytes
        if staged:
            try:
                data_batch._staged = staged
            except AttributeError:   # a batch type that takes no attribute
                return 0
        return nbytes

    def forward_backward(self, data_batch):
        """Fused fwd+bwd: one compiled XLA dispatch (see executor)."""
        assert self.binded and self.params_initialized
        self._load_batch(data_batch)
        with stepprof.phase("dispatch"):
            if self._monitor is not None:
                self._exec.forward(is_train=True)
            self._exec.forward_backward()

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    def update(self):
        """Reference module.py:631 + model.py _update_params(_on_kvstore)."""
        assert self.binded and self.params_initialized and self.optimizer_initialized
        self._params_dirty = True
        live = [(i, name, self._exec.grad_dict.get(name))
                for i, name in enumerate(self._param_names)
                if self._grad_req.get(name) != "null"
                and self._exec.grad_dict.get(name) is not None]
        if self._update_on_kvstore:
            # list push/pull: the kvstore applies every key's update in
            # one dispatch when the optimizer is fusable. The whole
            # push+apply+pull round-trip is gradient aggregation time.
            with stepprof.phase("sync", via="kvstore_update"):
                self._kvstore.push([i for i, _, _ in live],
                                   [g for _, _, g in live])
                self._kvstore.pull([i for i, _, _ in live],
                                   [self._exec.arg_dict[name]
                                    for _, name, _ in live])
        else:
            if self._kvstore:
                with stepprof.phase("sync", via="kvstore_reduce"):
                    self._kvstore.push([i for i, _, _ in live],
                                       [g for _, _, g in live])
                    self._kvstore.pull([i for i, _, _ in live],
                                       [g for _, _, g in live])
            if self._fused is None:
                from .. import optimizer as opt
                self._fused = opt.FusedApplier.resolve(self._updater)
            with stepprof.phase("opt_update",
                                fused=bool(self._fused)):
                if self._fused:
                    self._fused([i for i, _, _ in live],
                                [self._exec.arg_dict[name]
                                 for _, name, _ in live],
                                [g for _, _, g in live])
                else:
                    for i, name, grad in live:
                        # mxanalyze: allow(dispatch-amplification): documented fallback when FusedApplier.resolve declines (non-fusable optimizer); the fused path above is the default
                        self._updater(i, grad, self._exec.arg_dict[name])
            if self._updater is not None:
                self._note_optimizer_bytes(
                    list(self._updater.states.values()))

    def step_program(self):
        """The `CompiledProgram` of the fused train step (`_step`), for
        tools that read its lowered or compiled text; None before the first
        step and where the step falls back to forward_backward + update."""
        return self._fused_plan[3] if self._fused_plan else None

    def _step(self, data_batch):
        """One-dispatch train step: forward + backward + optimizer update in
        a SINGLE jitted XLA program (the reference needs two engine bulk
        segments for the same work, graph_executor.cc:1377 + the kvstore
        update; here the whole step is one device dispatch).

        Falls back to forward_backward()+update() whenever the fused form
        can't reproduce the exact semantics: kvstore in play (reduction or
        dist), non-fusable optimizer, or grad_req 'add'."""
        if self._fused_plan is None:
            self._fused_plan = self._build_fused_step()
        if self._fused_plan is False:
            self.forward_backward(data_batch)
            self.update()
            return
        from ..ndarray.ndarray import _from_data
        live_names, indices, fused, step_fn, _ = self._fused_plan
        self._load_batch(data_batch)
        exec_ = self._exec
        # the dispatch phase ends where the compiled call returns: its
        # end is the step program's enqueue instant, which the
        # benchmark's join with the device trace splits idle time on
        # (benchmark/timeline.py); keep everything after the call out
        with stepprof.phase("dispatch", site="module.fused_step"):
            arg_vals, aux_vals = exec_._gather()
            key = exec_._next_key()
            grad_args = {n: arg_vals[n] for n in exec_._grad_names}
            other_args = {n: v for n, v in arg_vals.items()
                          if n not in exec_._grad_names}
            weights = [exec_.arg_dict[n] for n in live_names]
            lrs, wds, rescale, state_vals = fused.prepare(indices, weights)
            # ledger the optimizer bytes BEFORE the dispatch: state_vals
            # is donated to the step (arg 7), so the old buffers must
            # not be touched once the program runs
            self._note_optimizer_bytes(state_vals)
            step_args = (grad_args, other_args, aux_vals, key, lrs, wds,
                         rescale, state_vals)
            if self._fused_donates_grads:
                # the last step's gradients, to be written over in place
                step_args += ({n: exec_.grad_dict[n]._data
                               for n in live_names},)
            outs, aux_up, new_ws, new_states, grads = step_fn(*step_args)
        from .. import xla_stats
        xla_stats.note_train_step(step_fn, batches=1)
        if stepprof.should_sync():
            # sampled sync: bracket the dispatch's results with a real
            # device wait so device_compute is a measured tile of THIS
            # step (the overlap estimator's ground truth); off the
            # sampled steps the device runs hidden behind host phases
            import jax
            from .. import threadsan
            if threadsan.ARMED:
                threadsan.note_dispatch("module._step.sampled_sync",
                                        kind="sync")
            with stepprof.phase("device_compute", synced=True) as _dc:
                jax.block_until_ready((outs, new_ws))
            stepprof.note_device_sample(_dc.seconds, batches=1)
        for name, val in aux_up.items():
            exec_.aux_dict[name]._data = val
        for w, nv in zip(weights, new_ws):
            w._data = nv
        # keep grad_dict live so batch callbacks / get_input_grads observe
        # the same state as the unfused path (the grads are program outputs
        # already on device; binding them is free of copies)
        for name, g in grads.items():
            dst = exec_.grad_dict.get(name)
            if dst is not None:
                # match Executor.forward_backward: a pre-allocated grad
                # buffer's dtype must not silently change after a fused step
                dst._data = g if g.dtype == dst.dtype else g.astype(dst.dtype)
        fused.commit_states(indices, new_states)
        exec_.outputs = [_from_data(v, exec_._ctx) for v in outs]
        self._params_dirty = True

    def _build_fused_step(self):
        """Build (live_names, indices, FusedApplier, jitted step, raw step)
        or False."""
        if self._kvstore is not None or self._updater is None \
                or self._monitor is not None:
            return False
        if getattr(self._exec, "_grouped", None) is not None:
            # group2ctx executors run chained per-device programs; the
            # single-jit fused step cannot span devices
            return False
        fused = opt.FusedApplier.resolve(self._updater)
        if not fused:
            return False
        live_names = [n for n in self._param_names
                      if self._grad_req.get(n) == "write"
                      and self._exec.grad_dict.get(n) is not None]
        if any(self._grad_req.get(n) not in ("null", "write")
               for n in self._param_names):
            return False  # grad_req 'add' needs the accumulating path
        if not live_names:
            return False
        import jax
        exec_ = self._exec
        _, fcompute, static = fused.update_op()
        n_outs = len(self._output_names)
        heads = tuple([None] * n_outs)

        def step(grad_args, other_args, aux_vals, key, lrs, wds, rescale,
                 state_vals, old_grads=None):
            outs, aux_up, grads = exec_._fwd_bwd_impl(
                grad_args, other_args, aux_vals, key, heads)
            new_ws, new_states = [], []
            out_grads = {}
            # mxanalyze: allow(dispatch-amplification): params have heterogeneous shapes/hyperparams so the per-param updates cannot stack into one lax.scan; the loop unrolls into ONE program (single dispatch), which is the point of the fused step
            for k, name in enumerate(live_names):
                params = dict(static)
                params["lr"] = lrs[k]
                params["wd"] = wds[k]
                params["rescale_grad"] = rescale
                g = grads[name].astype(grad_args[name].dtype)
                out_grads[name] = g
                with jax.named_scope("optimizer"):
                    upd_outs = fcompute(params, grad_args[name], g,
                                        *state_vals[k])
                new_ws.append(upd_outs[0])
                new_states.append(tuple(upd_outs[1:]))
            # non-param grads (inputs_need_grad) surface too
            for name, g in grads.items():
                if name not in out_grads:
                    out_grads[name] = g
            return outs, aux_up, new_ws, new_states, out_grads

        # donate the optimizer states (rebound after the call); params are
        # not donated by default — user code may hold views of the old
        # weight buffers. Under an EXPLICITLY selected SPMD policy
        # (spmd=.../MXNET_SPMD — not the implicit multi-device default,
        # which keeps the legacy buffer-lifetime guarantee) the step ALSO
        # donates the param buffers (grad_args, arg 0): old params are
        # rebound from the program outputs every step, and freeing them
        # halves transient param memory — the donate_argnums ask of
        # ROADMAP item 1 (MXNET_SPMD_DONATE=0 opts out).
        from .. import compiled as compiled_mod
        # inputs_need_grad puts the data/label buffers in grad_args too;
        # they are NOT rebound from program outputs after the step, so
        # donating arg 0 would leave them deleted — params-only donation
        # requires every grad_args leaf to be a rebound parameter
        spmd_donate = getattr(self, "_spmd_explicit", False) \
            and not self.inputs_need_grad \
            and compiled_mod.spmd_donate_enabled()
        # ... and the gradients of the step before (arg 8), which the step
        # rebinds from its outputs like the params: a program that only
        # writes over them does not use them, so they are kept for it
        donate = (0, 7, 8) if spmd_donate else (7,)
        donate = compiled_mod.donate_argnums_for(self._context[0], donate)
        step_fn = compiled_mod.tracked_jit(step, "module.fused_step",
                                           donate_argnums=donate,
                                           lineage=id(self),
                                           policy=self._spmd,
                                           **({"keep_unused": True}
                                              if 8 in donate else {}))
        self._fused_donates_grads = 8 in donate
        indices = [self._param_names.index(n) for n in live_names]
        return (live_names, indices, fused, step_fn, step)

    # -- scanned multi-batch step ---------------------------------------
    def _step_scan(self, data_batches):
        """Run ``len(data_batches)`` fused train steps in ONE device
        dispatch: each batch goes to the device on its own (`fit` has
        :meth:`prepare` put it there while the dispatch before runs), the K
        are stacked there (:meth:`stack_batches`), and a ``lax.scan``
        carries (params, optimizer states, aux, RNG key) through the K
        steps.

        TPU-native throughput feature with no reference analog: the
        reference pays one engine push per op per batch
        (graph_executor.cc:1377); the fused `_step` already collapses a
        step to one dispatch, and this collapses K steps to one — on a
        high-latency link (or with fast steps) training becomes
        device-bound instead of dispatch-bound. Used by ``fit(...,
        batches_per_dispatch=K)``.

        Returns the per-step stacked outputs (list over module outputs,
        each with leading axis K) for metric updates; grad_dict is NOT
        rebound (use plain `_step` when per-batch gradients are needed).

        ``data_batches`` may also be a prestacked dict from
        :meth:`stack_batches` — placement and stack then happened ahead
        of time, and the dict can be fed again and again.
        """
        if isinstance(data_batches, dict):
            K = next(iter(data_batches.values())).shape[0]
        else:
            K = len(data_batches)
            if K == 1:
                self._step(data_batches[0])
                return None
        if self._fused_plan is None:
            self._fused_plan = self._build_fused_step()
        # scan unroll factor: unrolling the step body removes the while
        # loop's per-iteration carry copies (XLA inserts HBM copies for
        # carried weights whose compute layout differs from the carry
        # layout) at the price of a K/unroll-times-larger program and
        # longer compile; set via Module.scan_unroll or
        # fit(..., scan_unroll=U). 1 = plain while loop.
        unroll = max(1, int(getattr(self, "scan_unroll", 1) or 1))
        plan_key = ("scan", K, unroll,
                    bool(getattr(self, "scan_donate_params", False)))
        scan_fn = None if self._scan_plans is None \
            else self._scan_plans.get(plan_key)
        if self._fused_plan is False or self.inputs_need_grad:
            return False  # caller steps per-batch (metrics stay per-batch)
        import jax
        from ..ndarray.ndarray import _from_data
        live_names, indices, fused, _, step_raw = self._fused_plan
        exec_ = self._exec
        if scan_fn is None:
            from jax import lax

            def scan_step(grad_args, consts, stacked, aux_vals, key,
                          lrs, wds, rescale, state_vals):
                def body(carry, xs):
                    ga, aux, sv, k = carry
                    k, sub = jax.random.split(k)
                    outs, aux_up, new_ws, new_states, _ = step_raw(
                        ga, {**consts, **xs}, aux, sub, lrs, wds, rescale, sv)
                    ga = dict(ga)
                    for n, w in zip(live_names, new_ws):
                        ga[n] = w
                    return (ga, {**aux, **aux_up}, list(new_states), k), \
                        tuple(outs)
                (ga, aux, sv, _), outs = lax.scan(
                    body, (grad_args, aux_vals, state_vals, key), stacked,
                    unroll=unroll)
                return ga, aux, sv, outs

            # donate the optimizer states only — matching _step's policy
            # (params are NOT donated: user code may hold raw views of the
            # old weight buffers, and fit() mixes scan and plain steps in
            # one epoch when the batch count isn't a multiple of K, so the
            # two paths must give the same buffer-lifetime guarantee).
            # Module.scan_donate_params=True (or an EXPLICIT spmd policy,
            # whose plain-step path donates params too) additionally
            # donates the params carry. compiled.donate_argnums_for
            # strips the set on CPU backends, which lack donation.
            from .. import compiled as compiled_mod
            spmd_donate = getattr(self, "_spmd_explicit", False) \
                and compiled_mod.spmd_donate_enabled()
            donate = (8,)
            if getattr(self, "scan_donate_params", False) or spmd_donate:
                donate = (0, 8)
            donate = compiled_mod.donate_argnums_for(self._context[0],
                                                     donate)
            scan_fn = compiled_mod.tracked_jit(scan_step,
                                               "module.scan_step",
                                               donate_argnums=donate,
                                               lineage=id(self),
                                               policy=self._spmd)
            if self._scan_plans is None:
                self._scan_plans = {}
            self._scan_plans[plan_key] = scan_fn

        if isinstance(data_batches, dict):
            placed = data_batches  # prestacked: staging already paid
        else:
            with stepprof.phase("h2d", via="stack_batches") as ph:
                placed, ph["staged_ahead"] = \
                    self._stack_batches(data_batches)
                ph["bytes"] = sum(v.nbytes for v in placed.values())

        # as in _step: the phase ends at the return of the compiled
        # call, the enqueue instant of this dispatch
        with stepprof.phase("dispatch", site="module.scan_step"):
            arg_vals, aux_vals = exec_._gather()
            grad_args = {n: arg_vals[n] for n in exec_._grad_names}
            consts = {n: v for n, v in arg_vals.items()
                      if n not in exec_._grad_names and n not in placed}
            weights = [exec_.arg_dict[n] for n in live_names]
            lrs, wds, rescale, state_vals = fused.prepare(indices, weights)
            # ledger BEFORE the dispatch — state_vals (arg 8) is donated
            self._note_optimizer_bytes(state_vals)
            key = exec_._next_key()
            ga, aux, sv, outs = scan_fn(grad_args, consts, placed,
                                        aux_vals, key, lrs, wds, rescale,
                                        state_vals)
        from .. import xla_stats
        # the scanned executable's FLOPs cover all K carried batches
        xla_stats.note_train_step(scan_fn, batches=K)
        if stepprof.should_sync():
            # sampled sync (see _step): one real device wait covering
            # the whole K-batch dispatch
            from .. import threadsan
            if threadsan.ARMED:
                threadsan.note_dispatch("module._step_scan.sampled_sync",
                                        kind="sync")
            with stepprof.phase("device_compute", synced=True,
                                batches=K) as _dc:
                jax.block_until_ready((ga, outs))
            stepprof.note_device_sample(_dc.seconds, batches=K)
        for name, val in aux.items():
            exec_.aux_dict[name]._data = val
        # rebind EVERY carried arg (not just the updated weights): with
        # scan_donate_params the old input buffers are invalid after the
        # call, including pass-through entries
        for name, val in ga.items():
            dst = exec_.arg_dict.get(name)
            if dst is not None:
                dst._data = val
        fused.commit_states(indices, sv)
        # K - 1, not -1: jax normalises a negative index with eager scalar
        # ops on the device, and with `fit` one dispatch ahead one of them
        # held this call until the dispatch BEFORE had ended (727 ms, in no
        # phase; PERF.md section 6, PR 31)
        exec_.outputs = [_from_data(o[K - 1], exec_._ctx) for o in outs]
        self._params_dirty = True
        return [_from_data(o, exec_._ctx) for o in outs]

    def stack_batches(self, data_batches):
        """Stage K DataBatches as ONE stacked (K, batch, ...) device array
        per input, placed/sharded for :meth:`_step_scan`.

        Every member goes to the bound device (the executor's sharding
        under ``context=[several]`` / ``spmd=``) on its own: the arrays
        :meth:`prepare` put there ahead where the batch carries them, else
        one asynchronous transfer each, host numpy and CPU-backend NDArray
        alike; one small program on that device then stacks the K members.
        Nothing is stacked on the host. `fit` prepares a group's members
        while the dispatch before it runs; a caller that holds the stacked
        dict can hand it to ``_step_scan`` again and again."""
        return self._stack_batches(data_batches)[0]

    def _stack_batches(self, data_batches):
        """:meth:`stack_batches`, with the bytes found staged ahead."""
        aheads = [_take_staged(b) for b in data_batches]
        columns = zip(*(self._batch_inputs(b) for b in data_batches))
        shardings = self._exec._shardings
        placed, found = {}, 0
        for column in columns:
            name = column[0][0]
            members = []
            for (_, arr), ahead in zip(column, aheads):
                ready = _staged_for(ahead, name, arr)
                if ready is None:
                    ready = self._place_input(name, arr)
                else:
                    found += ready.nbytes
                members.append(ready)
            stacked = _stack_members(*members)
            if shardings is not None and name in shardings:
                from jax.sharding import NamedSharding, PartitionSpec as P
                sh = shardings[name]
                stacked = jax.device_put(stacked, NamedSharding(
                    sh.mesh, P(*((None,) + tuple(sh.spec)))))
            placed[name] = stacked
        return placed, found

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._exec.outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return [self._exec.grad_dict.get(n) for n in self._data_names]

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return [self._exec.arg_dict[n] for n in self._state_names]

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        if states is not None:
            for name, arr in zip(self._state_names, states):
                self._exec.arg_dict[name][:] = arr
        else:
            for name in self._state_names:
                self._exec.arg_dict[name][:] = value

    def update_metric(self, eval_metric, labels):
        eval_metric.update_dict(dict(zip(self._label_names, labels or [])),
                                dict(zip(self._output_names, self._exec.outputs)))

    def install_monitor(self, mon):
        assert self.binded
        self._monitor = mon
        self._fused_plan = None
        self._scan_plans = None
        mon.install(self._exec)

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())


def _take_staged(data_batch):
    """What :meth:`Module.prepare` left on ``data_batch``, taken off it:
    {input name: (the array it was made from, the device array)}, or None.
    A staged array serves one use of the batch."""
    staged = getattr(data_batch, "_staged", None)
    if staged is not None:
        del data_batch._staged
    return staged


def _staged_for(staged, name, arr):
    """The device array staged for input ``name``, if it was made from
    ``arr`` itself (a batch whose arrays were swapped since is placed
    anew); else None."""
    src, ready = (staged or {}).get(name, (None, None))
    return ready if src is arr else None


@jax.jit
def _stack_members(*members):
    """K arrays of one placement as one (K, ...) array there, by one
    program on that placement."""
    return jnp.stack(members)


def _norm_shapes(shapes):
    from ..io import DataDesc
    out = []
    for s in shapes:
        if isinstance(s, DataDesc):
            out.append((s.name, tuple(s.shape)))
        else:
            out.append((s[0], tuple(s[1])))
    return out
