"""Executor: compiled whole-graph execution.

Parity with reference `include/mxnet/executor.h` / `src/executor/
graph_executor.cc` (Bind/SimpleBind, Forward/Backward, outputs, monitor
callback, shared-memory rebinding for bucketing).

TPU-native design (SURVEY.md §7 stage 5): instead of NNVM passes + per-op
engine pushes, binding builds a pure Python evaluator over the Symbol DAG and
`jax.jit`s it — the whole graph becomes ONE XLA computation per
(is_train, shapes) signature:

- memory planning        -> XLA buffer assignment (replaces PlanMemory)
- bulk exec segments     -> a single fused program (replaces graph_executor.cc:1377)
- gradient graph         -> `jax.vjp` over the evaluator (replaces Gradient pass)
- grad_req add/write     -> functional accumulation into grad buffers
- device placement       -> ctx -> jax.Device; `__ctx_group__` attrs reserved
                            for sharding annotations (parallel/)
- dynamic shapes         -> jit retraces per shape signature; executors share
                            parameter NDArrays (bucketing,
                            reference shared_buffer graph_executor.h:105)

Backward runs a fused forward+vjp XLA program: one full train step is one
device dispatch, matching (and beating) the reference's bulked engine model.
"""
from __future__ import annotations

import time
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .context import Context, cpu
from .ndarray.ndarray import NDArray, _from_data, zeros as nd_zeros
from .ops.registry import get_op
from .symbol.symbol import Symbol, _graph_infer

__all__ = ["Executor"]


def _truthy(v):
    return v in (True, 1) or str(v).lower() in ("true", "1")


def _consumer_map(sym: Symbol, nodes):
    """(id(node), out_idx) -> list of consuming nodes (None = graph
    output). Shared by the graph-optimization planners below."""
    consumers = {}
    for n in nodes:
        for src, oi in n.inputs:
            consumers.setdefault((id(src), oi), []).append(n)
    for nd_, i in sym._outputs:
        consumers.setdefault((id(nd_), i), []).append(None)
    return consumers


def _plan_conv_bias_bn_fold(sym: Symbol, nodes):
    """Graph-optimization pass: elide a conv bias that feeds straight into a
    BatchNorm over the same channel axis.

    BN's mean subtraction cancels any per-channel offset exactly, so the
    bias contributes NOTHING to the loss (its gradient is identically zero
    in real arithmetic) — yet computing that zero costs a full
    reduce over the (N, spatial..., C) output gradient per conv (~13% of
    ResNet-50 v1 device step time on TPU, where the Gluon zoo's
    BottleneckV1 1x1 convs carry biases, mirroring the reference
    gluon/model_zoo/vision/resnet.py:107,113). The rewrite drops the bias
    from the conv and hands it to the BN, which folds it into the running
    -mean aux update (train: running_mean tracks mean(x)+b; eval: normalize
    with running_mean-b) — bit-parity with the unfused graph up to bf16
    rounding of the elided add.

    Pure eval-time plan: returns {id(node): action} consulted by eval_fn;
    the shared Symbol is never mutated (other binds see the original
    graph). Skip with MXNET_FOLD_CONV_BIAS_BN=0. Skips BNs with
    use_global_stats (there the bias has a real gradient through the fixed
    -stats affine path)."""
    import os
    if os.environ.get("MXNET_FOLD_CONV_BIAS_BN", "1") == "0":
        return {}
    consumers = _consumer_map(sym, nodes)
    folds = {}
    for n in nodes:
        if n.op not in ("BatchNorm", "BatchNorm_v1") or not n.inputs:
            continue
        if _truthy(n.attrs.get("use_global_stats", False)):
            continue
        conv, oi = n.inputs[0]
        if conv.is_var() or conv.op != "Convolution" or oi != 0:
            continue
        if id(conv) in folds:
            continue
        attrs = conv.attrs
        if _truthy(attrs.get("no_bias", False)) or len(conv.inputs) < 3:
            continue
        kernel = tuple(attrs.get("kernel") or ())
        if not kernel:
            continue
        rank = len(kernel) + 2
        spec = "DHW"[3 - len(kernel):]
        layout = attrs.get("layout") or ("NC" + spec)
        if layout in (None, "None"):
            layout = "NC" + spec
        if layout == "NC" + spec:
            ch_axis = 1
        elif layout == "N" + spec + "C":
            ch_axis = rank - 1
        else:
            continue
        if int(n.attrs.get("axis", 1)) % rank != ch_axis:
            continue
        if len(consumers.get((id(conv), 0), [])) != 1:
            continue
        bias_src, bias_oi = conv.inputs[2]
        folds[id(conv)] = ("drop_bias",)
        folds[id(n)] = ("fold_bias", bias_src, bias_oi)
    return folds


def _plan_relu_pool_fold(sym: Symbol, nodes, folds):
    """Graph-optimization pass: fold a relu into the max-Pooling that is
    its only consumer.

    ``maxpool(relu(x)) == maximum(maxpool(x), 0)`` exactly, and the
    gradients agree up to measure-zero ties (grad reaches the window's
    argmax iff the window max is positive — the same positions the relu
    mask admits). The ResNet stem's relu feeds only the 3x3/2 maxpool; the
    fold saves a full read+write of the (N,112,112,64) activation forward
    and the standalone mask multiply backward (~1 ms/step on bf16 bs128).
    Skip with MXNET_FOLD_RELU_POOL=0."""
    import os
    if os.environ.get("MXNET_FOLD_RELU_POOL", "1") == "0":
        return
    consumers = _consumer_map(sym, nodes)
    for n in nodes:
        if n.op != "Pooling" or id(n) in folds or not n.inputs:
            continue
        if n.attrs.get("pool_type", "max") != "max":
            continue
        if n.attrs.get("pooling_convention", "valid") != "valid":
            # ceil-mode can emit windows covering ONLY padding: the
            # unfolded graph yields -inf there, the clamp would yield 0
            continue
        act, oi = n.inputs[0]
        if act.is_var() or act.op != "Activation" or oi != 0 \
                or id(act) in folds:
            continue
        if act.attrs.get("act_type") != "relu":
            continue
        if len(consumers.get((id(act), 0), [])) != 1:
            continue
        folds[id(act)] = ("bypass",)
        folds[id(n)] = ("fold_relu",)


def _scope_attr(node, key):
    """The value `mx.AttrScope(key=...)` left on ``node``, or None."""
    return node.attrs.get("__attrs__", {}).get(key)


def _plan_mirror_segments(sym: Symbol, nodes, folds):
    """Recomputation, expressed in the symbol (the reference's memory
    mirroring, `MXNET_BACKWARD_DO_MIRROR` with its ``force_mirroring`` /
    ``mirror_stage`` node attributes, graph_executor.cc:282): op nodes that
    carry the same ``mirror_stage`` (`mx.AttrScope(mirror_stage=...)`) and
    are contiguous in topological order, variables aside, form one segment.
    A training evaluation runs each segment under `jax.checkpoint`: the
    backward pass keeps the segment's inputs and recomputes the rest.

    Returns [(indices into ``nodes``, external inputs [(node id, output)],
    outputs used outside [(node id, output)])]; empty for a symbol without
    the attribute, whose evaluation is then what it always was."""
    runs, run, stage = [], [], None
    for i, n in enumerate(nodes):
        if n.is_var():
            continue
        here = _scope_attr(n, "mirror_stage")
        if run and here != stage:
            runs.append(run)
            run = []
        if here is not None:
            run.append(i)
        stage = here
    if run:
        runs.append(run)
    if not runs:
        return []
    consumers = _consumer_map(sym, nodes)
    segments = []
    for run in runs:
        inside = {id(nodes[i]) for i in run}
        ins, outs = [], []
        for i in run:
            n = nodes[i]
            sources = list(n.inputs)
            fold = folds.get(id(n))
            if fold is not None and fold[0] == "fold_bias":
                sources.append((fold[1], fold[2]))
            for src, oi in sources:
                if id(src) not in inside and (id(src), oi) not in ins:
                    ins.append((id(src), oi))
            outs.extend(
                (id(n), oi) for oi in range(n.num_outputs)
                if any(u is None or id(u) not in inside
                       for u in consumers.get((id(n), oi), ())))
        segments.append((run, ins, outs))
    return segments


def _build_eval(sym: Symbol, ctx=None):
    """Build eval_fn(arg_vals, aux_vals, key, is_train) -> (outs, aux_updates).

    Pure and traceable: one call under jit compiles the entire graph.
    """
    nodes = sym._topo_nodes()
    sym._mark_aux()
    out_index = [(id(n), i) for n, i in sym._outputs]
    folds = _plan_conv_bias_bn_fold(sym, nodes)
    _plan_relu_pool_fold(sym, nodes, folds)
    segments = _plan_mirror_segments(sym, nodes, folds)
    from . import telemetry
    telemetry.gauge(
        "remat_segments", "segments of the last bound graph that a training "
        "step recomputes in its backward pass (mirror_stage)").set(
            len(segments))

    def eval_node(seq, n, env, aux_updates, key, is_train):
        op = get_op(n.op)
        params = {k: v for k, v in n.attrs.items() if k != "__attrs__"}
        params["_ctx"] = ctx
        if op.need_train_flag:
            params["_is_train"] = is_train
        if op.need_rng:
            params["_rng_key"] = jax.random.fold_in(key, seq)
        fold = folds.get(id(n))
        if fold is not None:
            if fold[0] == "drop_bias":
                params["no_bias"] = True
            elif fold[0] == "fold_bias":
                params["_fold_bias"] = env[id(fold[1])][fold[2]]
            elif fold[0] == "fold_relu":
                params["_fold_relu"] = True
            elif fold[0] == "bypass":
                env[id(n)] = [env[id(n.inputs[0][0])][n.inputs[0][1]]]
                return
        ins = [env[id(src)][oi] for src, oi in n.inputs]
        scope = _scope_attr(n, "profiler_scope")
        if scope is None:
            outs = op.fcompute(params, *ins)
        else:
            # `mx.AttrScope(profiler_scope=...)`: the name a device trace
            # shows this node's operations under
            with jax.named_scope(scope):
                outs = op.fcompute(params, *ins)
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        n_out = op.n_out(params)
        if op.mutate_aux:
            for ai, new_val in zip(op.mutate_aux, outs[n_out:]):
                src, _ = n.inputs[ai]
                if src.is_var():
                    aux_updates[src.name] = new_val
            outs = outs[:n_out]
        env[id(n)] = list(outs)

    def eval_segment(segment, env, aux_updates, key):
        run, ins, outs = segment

        def body(vals, key):
            local, updates = {}, {}
            for (nid, oi), val in zip(ins, vals):
                local.setdefault(nid, {})[oi] = val
            for seq in run:
                eval_node(seq, nodes[seq], local, updates, key, True)
            return [local[nid][oi] for nid, oi in outs], updates

        vals, updates = jax.checkpoint(body)(
            [env[nid][oi] for nid, oi in ins], key)
        aux_updates.update(updates)
        for (nid, oi), val in zip(outs, vals):
            env.setdefault(nid, {})[oi] = val

    def eval_fn(arg_vals, aux_vals, key, is_train):
        env = {}
        aux_updates = {}
        recompute = {s[0][0]: s for s in segments} if is_train else {}
        covered = {seq for s in recompute.values() for seq in s[0]}
        for n in nodes:
            if not n.is_var():
                continue
            if n.name in arg_vals:
                env[id(n)] = [arg_vals[n.name]]
            elif n.name in aux_vals:
                env[id(n)] = [aux_vals[n.name]]
            else:
                raise MXNetError("unbound variable %s" % n.name)
        for seq, n in enumerate(nodes):
            if n.is_var():
                continue
            if seq in recompute:
                eval_segment(recompute[seq], env, aux_updates, key)
            elif seq not in covered:
                eval_node(seq, n, env, aux_updates, key, is_train)
        return [env[nid][i] for nid, i in out_index], aux_updates

    return eval_fn


class Executor:
    def __init__(self, symbol, ctx, arg_dict, grad_dict, aux_dict, grad_req,
                 shardings=None, group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx
        # name -> jax.sharding.Sharding for SPMD data parallelism (Module
        # with a multi-device context list); None = single-device executor
        self._shardings = shardings
        self.arg_dict = arg_dict            # name -> NDArray (shared, mutable)
        self.grad_dict = grad_dict          # name -> NDArray or None
        self.aux_dict = aux_dict
        self._grad_req = grad_req           # name -> 'write'|'add'|'null'
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()
        self._eval_fn = _build_eval(symbol, ctx)
        # CompiledPrograms (mxnet_tpu/compiled.py): one shared layer for
        # the signature cache, AOT warmup, donation, and compile
        # accounting (counters, retrace explanations, per-executable
        # FLOPs land in xla_stats). Lineage = the Symbol: executors
        # rebound over one graph (reshape/bucketing) diff as retraces;
        # unrelated models don't.
        from . import compiled as compiled_mod
        self._jit_fwd = compiled_mod.tracked_jit(
            self._eval_fn, "executor.forward", static_argnums=(3,),
            lineage=id(symbol))
        if shardings:
            # replicated placement on the same mesh, for the RNG key: a jit
            # whose args span the mesh rejects a single-device key
            from jax.sharding import NamedSharding, PartitionSpec
            any_s = next(iter(shardings.values()))
            self._repl_sharding = NamedSharding(any_s.mesh, PartitionSpec())
        else:
            self._repl_sharding = None
        self._grad_names = [n for n in self._arg_names
                            if grad_req.get(n, "null") != "null"]
        self._jit_fwd_bwd = compiled_mod.tracked_jit(
            self._fwd_bwd_impl, "executor.forward_backward",
            lineage=id(symbol))
        self._grouped = None
        self._group2ctx = group2ctx
        if group2ctx:
            from .group_exec import GroupedGraph, var_placements
            # var_placements is the single source of truth for "is this
            # bind effectively multi-device" — simple_bind used the same
            # call to home the parameters
            if var_placements(symbol, ctx, group2ctx):
                # per-group device placement (reference PlaceDevice pass):
                # chained per-device programs replace the single jit
                self._grouped = GroupedGraph(symbol, ctx, group2ctx,
                                             grad_names=self._grad_names)
                self._jit_fwd = self._grouped.forward
                self._jit_fwd_bwd = self._grouped.forward_backward
        self.outputs = []
        self._monitor = None
        self._out_avals = None
        self._fwd_snapshot = None

    # -- construction ----------------------------------------------------
    @staticmethod
    def simple_bind(symbol, ctx, grad_req="write", type_dict=None,
                    group2ctx=None, shared_exec=None, shared_buffer=None,
                    shardings=None, **kwargs):
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        arg_shapes_d, _, aux_shapes_d = _graph_infer(symbol, kwargs,
                                                     type_dict=type_dict)
        type_dict = type_dict or {}
        req = _norm_req(grad_req, arg_names, kwargs)
        if shardings is None and shared_exec is not None:
            shardings = shared_exec._shardings
        group_place = {}
        if group2ctx:
            from .group_exec import var_placements
            group_place = var_placements(symbol, ctx, group2ctx)

        def _make(name, shape, dt):
            # SPMD executors place every buffer with its mesh sharding up
            # front (params/aux replicated, batch args dp-sharded); the
            # reference instead allocates per-device executors
            # (executor_group.py:129) — here ONE program spans the mesh
            if shardings is not None and name in shardings:
                return _from_data(jnp.zeros(tuple(shape), dt,
                                            device=shardings[name]), ctx)
            # group2ctx: the variable lives on its group's device
            return nd_zeros(shape, ctx=group_place.get(name, ctx), dtype=dt)

        arg_dict = {}
        grad_dict = {}
        for name in arg_names:
            shape = arg_shapes_d.get(name)
            if shape is None:
                raise MXNetError("cannot infer shape of argument %s" % name)
            dt = type_dict.get(name, np.float32)
            if shared_exec is not None and name in shared_exec.arg_dict and \
                    shared_exec.arg_dict[name].shape == tuple(shape):
                arg_dict[name] = shared_exec.arg_dict[name]
                if req.get(name, "null") != "null":
                    grad_dict[name] = shared_exec.grad_dict.get(name)
            elif shared_buffer is not None and name in shared_buffer and \
                    shared_buffer[name].shape == tuple(shape):
                arg_dict[name] = shared_buffer[name]
            else:
                arg_dict[name] = _make(name, shape, dt)
                if shared_buffer is not None:
                    shared_buffer[name] = arg_dict[name]
            if req.get(name, "null") != "null" and name not in grad_dict:
                grad_dict[name] = _make(name, shape, dt)
        aux_dict = {}
        for name in aux_names:
            shape = aux_shapes_d.get(name)
            if shape is None:
                raise MXNetError("cannot infer shape of aux state %s" % name)
            if shared_exec is not None and name in shared_exec.aux_dict and \
                    shared_exec.aux_dict[name].shape == tuple(shape):
                aux_dict[name] = shared_exec.aux_dict[name]
            else:
                aux_dict[name] = _make(name, shape,
                                       type_dict.get(name, np.float32))
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict, req,
                        shardings=shardings, group2ctx=group2ctx)

    @staticmethod
    def bind(symbol, ctx, args, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        arg_dict = _to_dict(args, arg_names, "args")
        grad_dict = _to_dict(args_grad, arg_names, "args_grad") if args_grad else {}
        aux_dict = _to_dict(aux_states, aux_names, "aux_states") if aux_states else {}
        req = _norm_req(grad_req, arg_names, {})
        if args_grad is None:
            req = {n: "null" for n in arg_names}
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict, req,
                        group2ctx=group2ctx)

    # -- execution -------------------------------------------------------
    def _gather(self):
        arg_vals = {n: a._data for n, a in self.arg_dict.items()}
        aux_vals = {n: a._data for n, a in self.aux_dict.items()}
        return arg_vals, aux_vals

    def _next_key(self):
        from . import random as _random
        if self._repl_sharding is not None:
            return _random._split_chain(self._repl_sharding)
        return _random.next_key(self._ctx)

    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k in self.arg_dict:
                self.arg_dict[k][:] = v
        arg_vals, aux_vals = self._gather()
        key = self._next_key()
        if self._monitor is not None:
            outs, aux_up = self._monitored_eval(arg_vals, aux_vals, is_train,
                                                key)
        else:
            from . import profiler
            t0 = time.perf_counter()
            outs, aux_up = self._jit_fwd(arg_vals, aux_vals, key,
                                         bool(is_train))
            if profiler.aggregate_enabled():
                profiler.finish_timed("_executor_forward", t0, outs)
        if is_train:
            # snapshot of pre-update inputs + key so a following backward()
            # recomputes the IDENTICAL forward (same dropout mask, idempotent
            # aux updates) inside its fused fwd+vjp program
            self._fwd_snapshot = (arg_vals, aux_vals, key)
            for name, val in aux_up.items():
                self.aux_dict[name]._data = val
        self.outputs = [_from_data(v, self._ctx) for v in outs]
        return self.outputs

    def _fwd_bwd_impl(self, grad_args, other_args, aux_vals, key, head_grads):
        def f(ga):
            outs, aux_up = self._eval_fn({**other_args, **ga}, aux_vals, key, True)
            return outs, aux_up

        (outs, aux_up), vjp = jax.vjp(f, grad_args)
        cots = []
        # mxanalyze: allow(dispatch-amplification): loops over OUTPUT HEADS (O(1) arity), not layers — each head needs its own dtype-dependent cotangent construction
        for o, hg in zip(outs, head_grads):
            if hg is not None:
                cots.append(hg)
            elif jnp.issubdtype(o.dtype, jnp.inexact):
                cots.append(jnp.ones_like(o))
            else:
                cots.append(np.zeros(o.shape, jax.dtypes.float0))
        zero_aux = jax.tree.map(
            lambda a: np.zeros(a.shape, jax.dtypes.float0)
            if not jnp.issubdtype(a.dtype, jnp.inexact) else jnp.zeros_like(a),
            aux_up)
        (grads,) = vjp((cots, zero_aux))
        return outs, aux_up, grads

    def forward_backward(self, out_grads=None, _snapshot=None, **kwargs):
        """Fused forward+backward: one XLA dispatch per step (the fast path
        used by Module.fit; the reference analog is bulked exec of the full
        fwd+bwd graph)."""
        for k, v in kwargs.items():
            if k in self.arg_dict:
                self.arg_dict[k][:] = v
        if _snapshot is not None:
            arg_vals, aux_vals, key = _snapshot
        else:
            arg_vals, aux_vals = self._gather()
            key = self._next_key()
        grad_args = {n: arg_vals[n] for n in self._grad_names}
        other_args = {n: v for n, v in arg_vals.items()
                      if n not in self._grad_names}
        heads = _norm_head_grads(out_grads, len(self._output_names))
        from . import profiler
        t0 = time.perf_counter()
        outs, aux_up, grads = self._jit_fwd_bwd(
            grad_args, other_args, aux_vals, key, heads)
        if profiler.aggregate_enabled():
            profiler.finish_timed("_executor_forward_backward", t0, outs)
        from . import compiled as compiled_mod, xla_stats
        if isinstance(self._jit_fwd_bwd, compiled_mod.CompiledProgram):
            # the unfused train path: one fwd+bwd dispatch == one batch
            xla_stats.note_train_step(self._jit_fwd_bwd, batches=1)
        for name, val in aux_up.items():
            self.aux_dict[name]._data = val
        for name, g in grads.items():
            dst = self.grad_dict.get(name)
            if dst is None:
                continue
            if self._grad_req.get(name) == "add":
                dst._data = dst._data + g.astype(dst.dtype)
            else:
                dst._data = g.astype(dst.dtype)
        self.outputs = [_from_data(v, self._ctx) for v in outs]
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        """Reference Executor::Backward. Runs the fused fwd+vjp program (the
        forward recompute lives in the same XLA program, so cost matches a
        standard JAX grad step). Reuses the last training-forward's input/key
        snapshot so the recompute is bit-identical to the forward the caller
        observed (same dropout mask; aux updates idempotent)."""
        if isinstance(out_grads, NDArray):
            out_grads = [out_grads]
        self.forward_backward(out_grads=out_grads,
                              _snapshot=getattr(self, "_fwd_snapshot", None))

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Reference Executor::Reshape: new executor sharing param arrays."""
        shapes = {}
        for name in self._arg_names:
            if name in kwargs:
                shapes[name] = kwargs[name]
        new = Executor.simple_bind(self._symbol, self._ctx,
                                   grad_req=self._grad_req,
                                   shared_exec=self,
                                   group2ctx=self._group2ctx, **shapes)
        return new

    # -- monitor (reference graph_executor.h:71 monitor callback) --------
    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor = (callback, monitor_all)

    def _monitored_eval(self, arg_vals, aux_vals, is_train, key=None):
        """Eager per-node evaluation invoking the monitor callback on every
        node output (debug path; equivalent of the reference's per-op
        monitor executed between engine pushes)."""
        callback, monitor_all = self._monitor
        nodes = self._symbol._topo_nodes()
        env = {}
        aux_updates = {}
        if key is None:
            key = self._next_key()
        if self._grouped is not None:
            # grouped buffers are committed to different devices; the
            # eager monitor walk computes on ONE device, so stage
            # everything to the default device first (debug path — the
            # reference's monitor likewise serializes execution)
            dev = self._ctx.jax_device()
            arg_vals = {n: jax.device_put(v, dev) for n, v in arg_vals.items()}
            aux_vals = {n: jax.device_put(v, dev) for n, v in aux_vals.items()}
            key = jax.device_put(key, dev)
        for seq, n in enumerate(nodes):
            if n.is_var():
                env[id(n)] = [arg_vals.get(n.name, aux_vals.get(n.name))]
                if monitor_all:
                    callback(n.name, _from_data(env[id(n)][0], self._ctx))
                continue
            op = get_op(n.op)
            params = {k: v for k, v in n.attrs.items() if k != "__attrs__"}
            params["_ctx"] = self._ctx
            if op.need_train_flag:
                params["_is_train"] = bool(is_train)
            if op.need_rng:
                params["_rng_key"] = jax.random.fold_in(key, seq)
            ins = [env[id(src)][oi] for src, oi in n.inputs]
            outs = op.fcompute(params, *ins)
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            n_out = op.n_out(params)
            if op.mutate_aux:
                for ai, new_val in zip(op.mutate_aux, outs[n_out:]):
                    src, _ = n.inputs[ai]
                    if src.is_var():
                        aux_updates[src.name] = new_val
                outs = outs[:n_out]
            env[id(n)] = list(outs)
            for i, o in enumerate(outs):
                callback("%s_output%d" % (n.name, i) if len(outs) > 1
                         else n.name + "_output", _from_data(o, self._ctx))
        out_index = [(id(nd), i) for nd, i in self._symbol._outputs]
        return [env[nid][i] for nid, i in out_index], aux_updates

    # -- views -----------------------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._aux_names]

    @property
    def output_dict(self):
        return dict(zip(self._output_names, self.outputs))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, array in arg_params.items():
            if name in self.arg_dict:
                self.arg_dict[name][:] = array.astype(self.arg_dict[name].dtype)
            elif not allow_extra_params:
                raise ValueError("Find name \"%s\" that is not in the arguments" % name)
        if aux_params is None:
            return
        for name, array in aux_params.items():
            if name in self.aux_dict:
                self.aux_dict[name][:] = array.astype(self.aux_dict[name].dtype)
            elif not allow_extra_params:
                raise ValueError("Find name %s that is not in the auxiliary states" % name)


def _norm_req(grad_req, arg_names, kwargs):
    if isinstance(grad_req, str):
        return {n: grad_req for n in arg_names}
    if isinstance(grad_req, (list, tuple)):
        return dict(zip(arg_names, grad_req))
    if isinstance(grad_req, dict):
        out = {n: "null" for n in arg_names}
        out.update(grad_req)
        return out
    raise MXNetError("invalid grad_req")


def _to_dict(arrs, names, what):
    if isinstance(arrs, dict):
        return dict(arrs)
    if isinstance(arrs, (list, tuple)):
        if len(arrs) != len(names):
            raise MXNetError("Length of %s does not match number of names" % what)
        return dict(zip(names, arrs))
    raise MXNetError("%s must be list or dict" % what)


def _norm_head_grads(out_grads, n):
    if out_grads is None:
        return tuple([None] * n)
    if isinstance(out_grads, NDArray):
        out_grads = [out_grads]
    heads = []
    for g in out_grads:
        heads.append(g._data if isinstance(g, NDArray) else g)
    while len(heads) < n:
        heads.append(None)
    return tuple(heads)
