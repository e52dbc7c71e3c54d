"""Gluon Block / HybridBlock / SymbolBlock.

Parity with reference `python/mxnet/gluon/block.py:123,486` — define-by-run
modules whose `hybridize()` compiles the computation. TPU-native: hybridize
traces `hybrid_forward` through the NDArray layer directly into `jax.jit`
(the NDArray payload becomes a tracer), producing one XLA program per
(train-flag, input-shapes) signature. This subsumes the reference CachedOp
(`src/imperative/cached_op.cc:342`) including its bulk execution — and goes
further: the whole model is a single fused program.
"""
from __future__ import annotations

import copy
import re
import threading
import warnings
from collections import OrderedDict

import numpy as np
import jax

from ..base import MXNetError
from ..context import current_context
from ..ndarray.ndarray import NDArray, _from_data
from .. import ndarray as nd_mod
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope:
    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                from ..name import NameManager
                prefix = NameManager.current().get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        from ..name import Prefix
        self._name_scope = Prefix(self._block.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        self._name_scope.__exit__(ptype, value, trace)
        self._name_scope = None
        _BlockScope._current.value = self._old_scope


class Block:
    """Reference gluon/block.py:123 Block."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params,
                                                        self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(["  ({key}): {block}".format(
            key=key, block=_indent(repr(block), 2))
            for key, block in self._children.items()])
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and not \
                    isinstance(value, type(existing)):
                raise TypeError("Changing attribute type for {name} from "
                                "{type1} to {type2} is not allowed.".format(
                                    name=name, type1=type(existing),
                                    type2=type(value)))
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params, \
                "Overriding Parameter attribute %s is not allowed." % name
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        self._check_container_with_block()
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for cld in self._children.values():
            ret.update(cld.collect_params(select=select))
        return ret

    def _check_container_with_block(self):
        children = set(self._children.values())
        for k, v in self.__dict__.items():
            if isinstance(v, (list, tuple, dict)) and not k.startswith("__"):
                def _find(value):
                    if isinstance(value, Block) and value not in children:
                        warnings.warn("'%s' is an unregistered container with "
                                      "Blocks: %s." % (k, str(value)), stacklevel=3)
                    elif isinstance(value, (list, tuple)):
                        for x in value:
                            _find(x)
                    elif isinstance(value, dict):
                        for x in value.values():
                            _find(x)
                _find(v)

    def save_params(self, filename):
        """Deprecated in reference in favor of save_parameters; both kept."""
        self.collect_params().save(filename, strip_prefix=self.prefix)

    def save_parameters(self, filename):
        params = self._collect_params_with_prefix()
        from ..ndarray import save as nd_save
        nd_save(filename, {k: v.data() for k, v in params.items()})

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def load_params(self, filename, ctx=None, allow_missing=False,
                    ignore_extra=False):
        self.collect_params().load(filename, ctx, allow_missing, ignore_extra,
                                   self.prefix)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False):
        from ..ndarray import load as nd_load
        loaded = nd_load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        if not any("." in k for k in loaded.keys()):
            # legacy format saved with save_params
            del loaded
            self.collect_params().load(filename, ctx, allow_missing,
                                       ignore_extra, self.prefix)
            return
        if not allow_missing:
            for name in params.keys():
                assert name in loaded, \
                    "Parameter '%s' is missing in file '%s'" % (name, filename)
        for name in loaded:
            if not ignore_extra and name not in params:
                raise ValueError(
                    "Parameter '%s' loaded from file '%s' is not present in "
                    "this block" % (name, filename))
            if name in params:
                params[name]._load_init(loaded[name], ctx)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle.id] = hook
        return handle

    def apply(self, fn):
        for cld in self._children.values():
            cld.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        from ..initializer import Uniform
        self.collect_params().initialize(init or Uniform(), ctx, verbose,
                                         force_reinit)

    def hybridize(self, active=True, **kwargs):
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        summary = OrderedDict()
        hooks = []

        def _get_shape_str(args):
            def flatten(args):
                if not isinstance(args, (list, tuple)):
                    return [args], int(0)
                flat = []
                fmts = []
                for i in args:
                    arg, fmt = flatten(i)
                    flat.extend(arg)
                    fmts.append(fmt)
                return flat, fmts
            flat_args, fmts = flatten(args)
            return str([x.shape if isinstance(x, NDArray) else None
                        for x in flat_args])

        def _register_summary_hook(block):
            def _summary_hook(block, inputs, outputs):
                class_name = block.__class__.__name__
                block_idx = len(summary) - 1
                m_key = "%s-%i" % (class_name, block_idx + 1)
                summary[m_key] = OrderedDict()
                summary[m_key]["output_shape"] = _get_shape_str(outputs)
                params = 0
                summary[m_key]["trainable"] = 0
                summary[m_key]["shared"] = 0
                for p in block.params.values():
                    params += int(np.prod(p.shape)) if p.shape else 0
                    summary[m_key]["trainable"] += 0 if p.grad_req == "null" \
                        else int(np.prod(p.shape)) if p.shape else 0
                summary[m_key]["n_params"] = params
            hooks.append(block.register_forward_hook(_summary_hook))

        try:
            self.apply(_register_summary_hook)
            self(*inputs)
            line_format = "{:>20}  {:>42} {:>15}"
            print("-" * 80)
            print(line_format.format("Layer (type)", "Output Shape", "Param #"))
            print("=" * 80)
            total_params = 0
            trainable_params = 0
            for layer in summary:
                print(line_format.format(layer,
                                         str(summary[layer]["output_shape"]),
                                         summary[layer]["n_params"]))
                total_params += summary[layer]["n_params"]
                trainable_params += summary[layer]["trainable"]
            print("=" * 80)
            print("Total params: " + str(total_params))
            print("Trainable params: " + str(trainable_params))
            print("-" * 80)
        finally:
            for h in hooks:
                h.detach()


class _HookHandle:
    _counter = [0]

    def __init__(self, hooks_dict):
        _HookHandle._counter[0] += 1
        self.id = _HookHandle._counter[0]
        self._hooks_dict = hooks_dict

    def detach(self):
        self._hooks_dict.pop(self.id, None)


def _indent(s_, num_spaces):
    lines = s_.split("\n")
    first = lines.pop(0)
    lines = [(num_spaces * " ") + line for line in lines]
    return "\n".join([first] + lines)


class HybridBlock(Block):
    """Reference gluon/block.py:486. `hybridize()` => jit-compiled forward."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_jit = None
        self._flags = {}
        self._param_order = None

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, HybridBlock):
            self._clear_cached_op()

    def register_child(self, block, name=None):
        if not isinstance(block, HybridBlock) and not isinstance(block, SymbolBlock):
            raise ValueError(
                "Children of HybridBlock must also be HybridBlock, "
                "but %s has type %s." % (str(block), str(type(block))))
        super().register_child(block, name)
        self._clear_cached_op()

    def hybridize(self, active=True, **kwargs):
        self._active = active
        if "remat" not in kwargs:
            # reference env MXNET_BACKWARD_DO_MIRROR (docs/faq/env_var.md
            # there): recompute activations in backward; here it defaults
            # hybridize(remat=...) to jax.checkpoint
            import os
            if os.environ.get("MXNET_BACKWARD_DO_MIRROR", "0") == "1":
                kwargs["remat"] = True
        self._flags = kwargs
        self._clear_cached_op()
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def _clear_cached_op(self):
        self._cached_jit = None
        self._param_order = None

    def infer_shape(self, *args):
        """Deferred-init: run an abstract forward to learn param shapes."""
        self._deferred_infer(args)

    def _deferred_infer(self, args):
        # Run eagerly with real data once all params that have shapes are
        # initialized; params without shape get them from first use inside
        # layer code (each layer implements shape inference in hybrid_forward
        # preamble via _finish_deferred or weight shape hooks).
        for child in self._children.values():
            pass

    def _build_jit(self):
        params = self._collect_all_params()
        names = sorted(params.keys())
        self._param_order = names
        block = self

        def traced(param_vals, key, is_train, ctx, *input_vals):
            from .. import autograd, random as _random
            from ..ops.invoke import _TLS as _invoke_tls
            # a tracer knows no device: the trace wraps its tracers in
            # `ctx`, the context of the call that caused it, which ops
            # that choose a lowering per device (the space-to-depth stem,
            # the fused LSTM) read as `_ctx`. Without it every op of a net
            # hybridized on the chip saw cpu(0) and took the CPU lowering.
            # It is a static argument: one trace for each context, so a
            # net moved with reset_ctx never replays the other's lowering.
            param_nds = {n: _from_data(v, ctx)
                         for n, v in zip(names, param_vals)}
            input_nds = [_from_data(v, ctx) if v is not None else None
                         for v in input_vals]
            with _ParamOverride(block, param_nds):
                with _random.key_scope(key):
                    saved_rec = autograd.set_recording(False)
                    saved_train = autograd.set_training(is_train)
                    # a parent's suppress_aux_writeback() warmup must not
                    # leak into THIS trace: the aux skip would be baked
                    # into the cached program forever (child BN stats
                    # would never update)
                    saved_aux = getattr(_invoke_tls, "no_aux", False)
                    _invoke_tls.no_aux = False
                    try:
                        out = block._forward_impl(*input_nds)
                    finally:
                        autograd.set_recording(saved_rec)
                        autograd.set_training(saved_train)
                        _invoke_tls.no_aux = saved_aux
            # mutate-aux writebacks (BatchNorm moving stats) rebound the
            # tracer NDArrays' ._data inside the trace; surface them as
            # outputs or the updates are silently DISCARDED when
            # _ParamOverride restores the real buffers (hybridized training
            # would freeze BN statistics)
            aux_up = {n: param_nds[n]._data
                      for n, v in zip(names, param_vals)
                      if param_nds[n]._data is not v}
            if isinstance(out, (list, tuple)):
                return tuple(o._data for o in out), aux_up
            return (out._data,), aux_up

        if self._flags.get("remat") or self._flags.get("static_alloc") == "remat":
            # rematerialize activations in backward instead of storing
            # them — the TPU analog of MXNET_BACKWARD_DO_MIRROR
            # (docs/architecture/note_memory.md); usage:
            # net.hybridize(remat=True)
            traced = jax.checkpoint(traced, static_argnums=(2, 3))
        from .. import compiled as compiled_mod
        # one CompiledProgram per hybridized block: retraces (shape/dtype
        # churn at the block's inputs) surface as jit_retraces_total{site=}
        # with an explained signature diff; lineage = this block, so
        # rebuilt jits of ONE net diff while unrelated nets never
        # cross-diff
        self._cached_jit = compiled_mod.tracked_jit(
            traced, "gluon.hybrid_forward", static_argnums=(2, 3),
            lineage=id(self))

    def _collect_all_params(self):
        out = {}
        for name, p in self.collect_params().items():
            out[name] = p
        return out

    def _call_cached(self, *args):
        from .. import autograd, random as _random
        if self._cached_jit is None:
            self._build_jit()
        params = self._collect_all_params()
        names = self._param_order
        param_nds = [params[n].data() for n in names]
        param_vals = [p._data for p in param_nds]
        input_vals = [a._data if isinstance(a, NDArray) else a for a in args]
        ctx = next((a.ctx for a in args if isinstance(a, NDArray)),
                   param_nds[0].ctx if param_nds else None)
        key_anchor = param_vals[0] if param_vals else (
            input_vals[0] if input_vals else None)
        key = _random.next_key_like(key_anchor)
        is_train = autograd.is_training()

        if autograd.is_recording():
            # differentiable path: vjp through the jitted program; aux
            # (BN moving stats) rides along undifferentiated
            def f(pvals, ivals):
                return self._cached_jit(pvals, key, is_train, ctx, *ivals)
            outs, vjp_fn, aux_up = jax.vjp(f, param_vals, input_vals,
                                           has_aux=True)
            tape_inputs = param_nds + [a for a in args if isinstance(a, NDArray)]

            def node_vjp(cots):
                p_cots, i_cots = vjp_fn(tuple(cots))
                return list(p_cots) + list(i_cots)

            node = autograd.Node(node_vjp, tape_inputs,
                                 [o.shape for o in outs],
                                 [np.dtype(o.dtype) for o in outs],
                                 name=self.name)
            out_nds = [_from_data(o, ctx) for o in outs]
            for i, o in enumerate(out_nds):
                o._autograd_node = (node, i)
        else:
            outs, aux_up = self._cached_jit(param_vals, key, is_train, ctx,
                                            *input_vals)
            out_nds = [_from_data(o, ctx) for o in outs]
        # commit mutated aux states (BN moving stats) back to the params
        for n, v in aux_up.items():
            params[n].data()._data = v
        return out_nds[0] if len(out_nds) == 1 else tuple(out_nds)

    def _forward_impl(self, *args):
        """Eager forward via hybrid_forward with params injected.

        Symbol inputs reroute to the symbolic tracer so export works even
        for blocks whose hybrid_forward invokes children through
        `child._forward_impl` (the model-zoo idiom).

        Deferred init (reference block.py deferred shape inference): a leaf
        layer with unknown param shapes implements `_infer_shapes(x)`; it
        runs on first forward, after which the params materialise."""
        from .. import symbol as sym_mod
        if args and isinstance(args[0], sym_mod.Symbol):
            return self._symbolic_forward(*args)
        if any(p._deferred_init for p in self._reg_params.values()):
            self._infer_shapes(*args)
            for p in self._reg_params.values():
                if p._deferred_init:
                    p._finish_deferred_init()
        params = {k: v.data() for k, v in self._reg_params.items()}
        return self.hybrid_forward(nd_mod, *args, **params)

    def _infer_shapes(self, *args):
        """Override in leaf layers to fill deferred param shapes from input."""

    def forward(self, x, *args):
        from .. import symbol as sym_mod
        if isinstance(x, sym_mod.Symbol):
            # child invoked during symbolic tracing (export/_trace_symbol)
            return self._symbolic_forward(x, *args)
        if self._active:
            try:
                return self._call_cached(x, *args)
            except DeferredInitializationError:
                # one eager pass materialises deferred params, then compile.
                # Its aux side effects (BN moving-stat updates) are rolled
                # back: the compiled call that follows performs the SAME
                # update (aux rides out of the cached program), and a
                # double step would diverge from the eager trajectory.
                self._clear_cached_op()
                from ..ops.invoke import suppress_aux_writeback
                with suppress_aux_writeback():
                    self._forward_impl(x, *args)
                return self._call_cached(x, *args)
        return self._forward_impl(x, *args)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def export(self, path, epoch=0):
        """Reference HybridBlock.export (block.py:665): symbol JSON + params."""
        from .. import symbol as sym_mod
        sym = self._trace_symbol()
        sym.save("%s-symbol.json" % path)
        arg_dict = {}
        for name, param in self.collect_params().items():
            arg_dict["arg:%s" % name] = param.data()
        from ..ndarray import save as nd_save
        nd_save("%s-%04d.params" % (path, epoch), arg_dict)

    def _trace_symbol(self):
        """Build a Symbol by running hybrid_forward with symbol inputs."""
        from .. import symbol as sym_mod
        data = sym_mod.var("data")
        out = self._symbolic_forward(data)
        if isinstance(out, (list, tuple)):
            out = sym_mod.Group(list(out))
        return out

    def _symbolic_forward(self, *args):
        """Symbolic analog of _forward_impl: hybrid_forward with the
        symbol module and param Variables; child blocks invoked inside
        hybrid_forward route back here via forward()'s Symbol check."""
        params = {k: v.var() for k, v in self._reg_params.items()}
        from .. import symbol as sym_mod
        return self.hybrid_forward(sym_mod, *args, **params)


class _ParamOverride:
    """Temporarily replace parameter data with tracer-backed NDArrays during
    jit tracing of a HybridBlock."""

    def __init__(self, block, param_nds):
        self._block = block
        self._param_nds = param_nds
        self._saved = {}

    def __enter__(self):
        params = self._block.collect_params()
        for name, nd in self._param_nds.items():
            p = params[name]
            self._saved[name] = p._data
            p._data = nd
        return self

    def __exit__(self, *a):
        params = self._block.collect_params()
        for name, old in self._saved.items():
            params[name]._data = old
        return False


class SymbolBlock(HybridBlock):
    """Wrap a Symbol + inputs into a Block (reference gluon/block.py:736)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=params)
        from .. import symbol as sym_mod
        if isinstance(inputs, sym_mod.Symbol):
            inputs = [inputs]
        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(list(outputs))
        self._symbol = outputs
        self._input_names = [i.name for i in inputs]
        arg_names = set(outputs.list_arguments())
        aux_names = set(outputs.list_auxiliary_states())
        # param names must stay EXACTLY the symbol's input names (no block
        # prefix) so exported .params files bind by name
        from .parameter import Parameter
        for name in outputs.list_inputs():
            if name not in self._input_names:
                grad_req = "null" if name in aux_names else "write"
                # consult shared params (the params= feature-extractor
                # idiom) before creating a fresh deferred Parameter
                existing = self.params._get_impl(name) \
                    if hasattr(self.params, "_get_impl") else None
                if existing is not None:
                    self.params._params[name] = existing
                elif name not in self.params._params:
                    self.params._params[name] = Parameter(
                        name, allow_deferred_init=True, grad_req=grad_req)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod
        sym = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(i) for i in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            from ..ndarray import load as nd_load
            loaded = nd_load(param_file)
            for k, v in loaded.items():
                name = k.split(":", 1)[-1]
                if name in ret.params.keys():
                    ret.params[name]._load_init(v, ctx)
        return ret

    def forward(self, x, *args):
        from ..executor import Executor
        inputs = [x] + list(args)
        arg_dict = {}
        for name, val in zip(self._input_names, inputs):
            arg_dict[name] = val
        for name, p in self.params.items():
            arg_dict[name] = p.data()
        aux_names = set(self._symbol.list_auxiliary_states())
        args_d = {k: v for k, v in arg_dict.items() if k not in aux_names}
        aux_d = {k: v for k, v in arg_dict.items() if k in aux_names}
        exe = Executor.bind(self._symbol, x.ctx, args_d, aux_states=aux_d)
        outs = exe.forward(is_train=False)
        return outs[0] if len(outs) == 1 else outs

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError
