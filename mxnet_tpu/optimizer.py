"""Optimizers (reference `python/mxnet/optimizer.py`, 1,519 LoC).

Registry + Updater, with per-parameter lr/wd multipliers, lr scheduling,
gradient rescale/clip and multi-precision (fp32 master weights for
bf16/fp16 params — reference SGD multi_precision). The per-parameter update
itself runs as a registered on-device op (`ops/optimizer_ops.py`), mirroring
how the reference registers updates as operators so they execute inside the
engine (`src/operator/optimizer_op.cc`).
"""
from __future__ import annotations

import math
import pickle

import numpy as np

from .base import MXNetError
from .ndarray import NDArray, zeros
from .ops.invoke import invoke

__all__ = ["Optimizer", "SGD", "NAG", "Signum", "Adam", "AdaGrad", "AdaDelta",
           "FusedApplier",
           "RMSProp", "Ftrl", "FTML", "DCASGD", "LBSGD", "SGLD", "Test",
           "Updater", "get_updater", "create", "register"]



_SPARSE_ROW_JIT = {}


def _is_lazy_rowsparse(grad):
    """Row-sparse gradient still carrying its compact payload — the state
    the O(nnz) lazy update paths key on."""
    from .ndarray.sparse import RowSparseNDArray
    return isinstance(grad, RowSparseNDArray) and grad.has_compact()


def _sparse_row_update(kind, weight, grad, states, scalars):
    """O(nnz) lazy row update over a compact row-sparse gradient (reference
    `src/operator/optimizer_op.cc:287-330,610` SGDUpdateRspImpl /
    AdamUpdateRspImpl): gather the touched rows of the weight/state, update
    them in f32, scatter back. Work and memory scale with nnz, not the
    dense row count.

    TPU form: nnz pads to the next pow2 (bounded jit cache, one compiled
    program per bucket); padded lanes use an out-of-range row index whose
    scatter is dropped (`mode='drop'`)."""
    import jax
    import jax.numpy as jnp

    vals, idx = grad.compact()
    rows = weight.shape[0]
    n = int(vals.shape[0])
    if n == 0:
        return
    bucket = 1 << (n - 1).bit_length()
    pad = bucket - n
    if pad:
        idx = jnp.concatenate(
            [idx, jnp.full((pad,), rows, idx.dtype)])
        vals = jnp.concatenate(
            [vals, jnp.zeros((pad,) + vals.shape[1:], vals.dtype)])
    key = (kind, tuple(weight.shape), str(weight.dtype), bucket,
           tuple(sorted(scalars)))
    fn = _SPARSE_ROW_JIT.get(key)
    if fn is None:
        def kernel(w, sts, idx, vals, sc):
            g = vals.astype(jnp.float32) * sc["rescale_grad"]
            if "clip_gradient" in sc:
                g = jnp.clip(g, -sc["clip_gradient"], sc["clip_gradient"])
            # padded lanes gather a clamped row (garbage) and scatter with
            # mode='drop' — no effect on the result
            wr = w[idx].astype(jnp.float32)
            g = g + sc["wd"] * wr
            if kind == "sgd":
                neww = w.at[idx].add((-sc["lr"] * g).astype(w.dtype),
                                     mode="drop")
                return neww, sts
            if kind == "sgd_mom":
                (m,) = sts
                newm = sc["momentum"] * m[idx] + g
                neww = w.at[idx].add((-sc["lr"] * newm).astype(w.dtype),
                                     mode="drop")
                return neww, (m.at[idx].set(newm, mode="drop"),)
            if kind == "adam":
                m, v = sts
                newm = sc["beta1"] * m[idx] + (1 - sc["beta1"]) * g
                newv = sc["beta2"] * v[idx] + (1 - sc["beta2"]) * g * g
                upd = sc["lr"] * newm / (jnp.sqrt(newv) + sc["epsilon"])
                neww = w.at[idx].add((-upd).astype(w.dtype), mode="drop")
                return neww, (m.at[idx].set(newm, mode="drop"),
                              v.at[idx].set(newv, mode="drop"))
            if kind == "adagrad":
                (h,) = sts
                newh = h[idx] + g * g
                upd = sc["lr"] * g / (jnp.sqrt(newh) + sc["epsilon"])
                neww = w.at[idx].add((-upd).astype(w.dtype), mode="drop")
                return neww, (h.at[idx].set(newh, mode="drop"),)
            raise ValueError(kind)
        fn = jax.jit(kernel)
        _SPARSE_ROW_JIT[key] = fn
    st_vals = tuple(s._data for s in states)
    sc = {k: float(v) for k, v in scalars.items()}
    neww, newst = fn(weight._data, st_vals, idx, vals, sc)
    weight._data = neww
    for s, ns in zip(states, newst):
        s._data = ns


def _state_zeros(weight, dtype=None):
    """Optimizer state co-located with the weight: same device — or same
    mesh sharding when the weight belongs to an SPMD (multi-device) module —
    so the fused update's jit sees a consistent placement set."""
    import jax.numpy as jnp
    from .base import device_of
    from .ndarray.ndarray import _from_data
    dev = device_of(weight._data)
    return _from_data(jnp.zeros(weight.shape, dtype or weight.dtype,
                                device=dev), weight.context)


class Optimizer:
    opt_registry = {}

    @staticmethod
    def register(klass):
        name = klass.__name__.lower()
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError("Cannot find optimizer %s" % name)

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        if param_idx2name is None:
            param_idx2name = {}
        if not isinstance(param_idx2name, dict):
            raise ValueError("param_idx2name should be a dict of param indexes to names.")
        self.idx2name = param_idx2name.copy()
        self.sym_info = (sym.attr_dict(), sym.list_arguments()) if sym is not None else ()
        self.param_dict = param_dict if param_dict else {}

    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        """fp32 master weight for low-precision params (reference mp_sgd)."""
        weight_master_copy = None
        if self.multi_precision and weight.dtype in (np.float16, np.dtype("bfloat16")):
            weight_master_copy = weight.astype("float32")
            return (weight_master_copy,) + (self.create_state(index, weight_master_copy),)
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError()

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and isinstance(state, tuple) and isinstance(state[0], NDArray) \
                and state[0].dtype == np.float32 and weight.dtype != np.float32:
            weight32, inner = state[0], state[1]
            g32 = grad.astype("float32")
            self.update(index, weight32, g32, inner)
            weight[:] = weight32.astype(weight.dtype)
        else:
            self.update(index, weight, grad, state)

    @property
    def learning_rate(self):
        """Current LR: scheduler(num_update) when a scheduler is set
        (reference optimizer.py Optimizer.learning_rate)."""
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise UserWarning("LRScheduler of the optimizer has already been defined.")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = {}
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__lr_mult__" in attr[name]:
                    self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__wd_mult__" in attr[name]:
                    self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    def _common_kwargs(self, index):
        kw = {"lr": self._get_lr(index), "wd": self._get_wd(index),
              "rescale_grad": self.rescale_grad}
        if self.clip_gradient:
            kw["clip_gradient"] = self.clip_gradient
        return kw


register = Optimizer.register


@register
class SGD(Optimizer):
    """SGD (+momentum, multi-precision) — reference optimizer.py SGD."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return _state_zeros(weight, dtype="float32")
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        if self.lazy_update and _is_lazy_rowsparse(grad):
            # O(nnz) row update (reference SGDUpdateRspImpl lazy_update)
            if state is not None:
                kw["momentum"] = self.momentum
                _sparse_row_update("sgd_mom", weight, grad, (state,), kw)
            else:
                _sparse_row_update("sgd", weight, grad, (), kw)
            return
        if state is not None:
            kw["momentum"] = self.momentum
            invoke("sgd_mom_update", [weight, grad, state], kw, out=weight)
        else:
            invoke("sgd_update", [weight, grad], kw, out=weight)

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and isinstance(state, tuple) and len(state) == 2 \
                and isinstance(state[0], NDArray) and state[0].dtype == np.float32 \
                and weight.dtype != np.float32:
            weight32, mom = state
            self._update_count(index)
            kw = self._common_kwargs(index)
            if mom is not None:
                kw["momentum"] = self.momentum
                invoke("mp_sgd_mom_update", [weight, grad, mom, weight32], kw, out=weight)
            else:
                invoke("mp_sgd_update", [weight, grad, weight32], kw, out=weight)
        else:
            self.update(index, weight, grad, state)


@register
class NAG(SGD):
    """Nesterov accelerated SGD (reference optimizer.py NAG)."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        g = g + wd * weight
        if state is not None:
            state[:] = self.momentum * state + g
            weight[:] = weight - lr * (self.momentum * state + g)
        else:
            weight[:] = weight - lr * g


@register
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return _state_zeros(weight, dtype="float32")
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        kw["wd_lh"] = self.wd_lh
        if state is not None:
            kw["momentum"] = self.momentum
            invoke("signum_update", [weight, grad, state], kw, out=weight)
        else:
            invoke("signsgd_update", [weight, grad], kw, out=weight)


@register
class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (_state_zeros(weight, dtype="float32"),
                _state_zeros(weight, dtype="float32"))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        kw = self._common_kwargs(index)
        coef1 = 1. - self.beta1 ** t
        coef2 = 1. - self.beta2 ** t
        kw["lr"] = kw["lr"] * math.sqrt(coef2) / coef1
        kw.update({"beta1": self.beta1, "beta2": self.beta2, "epsilon": self.epsilon})
        mean, var = state
        if self.lazy_update and _is_lazy_rowsparse(grad):
            # O(nnz) row update (reference AdamUpdateRspImpl lazy_update)
            _sparse_row_update("adam", weight, grad, (mean, var), kw)
            return
        invoke("adam_update", [weight, grad, mean, var], kw, out=weight)


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return _state_zeros(weight, dtype="float32")

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        if _is_lazy_rowsparse(grad):
            # O(nnz) row update (reference AdagradUpdateRspImpl)
            kw = {"lr": lr, "wd": wd, "rescale_grad": self.rescale_grad,
                  "epsilon": self.float_stable_eps}
            if self.clip_gradient is not None:
                kw["clip_gradient"] = self.clip_gradient
            _sparse_row_update("adagrad", weight, grad, (state,), kw)
            return
        g = grad.astype("float32") * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        g = g + wd * weight.astype("float32")
        state[:] = state + g * g
        weight[:] = (weight.astype("float32") -
                     lr * g / (state.sqrt() + self.float_stable_eps)).astype(weight.dtype)


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (_state_zeros(weight),
                _state_zeros(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        acc_g, acc_delta = state
        acc_g[:] = self.rho * acc_g + (1. - self.rho) * g * g
        current_delta = ((acc_delta + self.epsilon).sqrt() /
                         (acc_g + self.epsilon).sqrt()) * g
        acc_delta[:] = self.rho * acc_delta + (1. - self.rho) * current_delta * current_delta
        weight[:] = weight - current_delta - wd * weight


@register
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon

    def create_state(self, index, weight):
        if self.centered:
            return (_state_zeros(weight, dtype="float32"),
                    _state_zeros(weight, dtype="float32"),
                    _state_zeros(weight, dtype="float32"))
        return _state_zeros(weight, dtype="float32")

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        kw.update({"gamma1": self.gamma1, "epsilon": self.epsilon})
        if self.centered:
            n, g, delta = state
            kw["gamma2"] = self.gamma2
            invoke("rmspropalex_update", [weight, grad, n, g, delta], kw, out=weight)
        else:
            invoke("rmsprop_update", [weight, grad, state], kw, out=weight)


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (_state_zeros(weight, dtype="float32"),
                _state_zeros(weight, dtype="float32"))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        kw.update({"lamda1": self.lamda1, "beta": self.beta})
        z, n = state
        invoke("ftrl_update", [weight, grad, z, n], kw, out=weight)


@register
class FTML(Optimizer):
    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (_state_zeros(weight, dtype="float32"),
                _state_zeros(weight, dtype="float32"),
                _state_zeros(weight, dtype="float32"))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        kw.update({"beta1": self.beta1, "beta2": self.beta2,
                   "epsilon": self.epsilon, "t": self._index_update_count[index]})
        d, v, z = state
        invoke("ftml_update", [weight, grad, d, v, z], kw, out=weight)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (_state_zeros(weight), weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        mom, previous_weight = state
        delta = -lr * (g + wd * weight + self.lamda * g * g * (weight - previous_weight))
        if mom is not None:
            mom[:] = self.momentum * mom + delta
            delta = mom
        previous_weight[:] = weight
        weight[:] = weight + delta


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics."""

    def update(self, index, weight, grad, state):
        from .ndarray import random as nd_random
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        weight[:] = weight - lr / 2 * (g + wd * weight) + \
            nd_random.normal(0, math.sqrt(lr), shape=weight.shape,
                             ctx=weight.context, dtype="float32").astype(weight.dtype)


@register
class LBSGD(SGD):
    """Large-batch SGD with LARS-style layer-wise adaptation
    (reference optimizer.py LBSGD)."""

    def __init__(self, warmup_strategy="linear", warmup_epochs=5,
                 batch_scale=1, updates_per_epoch=32, begin_epoch=0,
                 num_epochs=60, **kwargs):
        super().__init__(**kwargs)
        self.warmup_strategy = warmup_strategy
        self.warmup_epochs = warmup_epochs
        self.batch_scale = batch_scale
        self.updates_per_epoch = updates_per_epoch
        self.init_updates = begin_epoch * updates_per_epoch
        self.num_epochs = num_epochs
        self.adaptive = True

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        if self.adaptive:
            wnorm = float(weight.norm().asscalar())
            gnorm = float(grad.norm().asscalar()) * self.rescale_grad
            if wnorm > 0 and gnorm > 0:
                lr = lr * 0.001 * wnorm / (gnorm + wd * wnorm)
        kw = {"lr": lr, "wd": wd, "rescale_grad": self.rescale_grad}
        if self.clip_gradient:
            kw["clip_gradient"] = self.clip_gradient
        if state is not None:
            kw["momentum"] = self.momentum
            invoke("sgd_mom_update", [weight, grad, state], kw, out=weight)
        else:
            invoke("sgd_update", [weight, grad], kw, out=weight)


@register
class Test(Optimizer):
    def create_state(self, index, weight):
        return _state_zeros(weight)

    def update(self, index, weight, grad, state):
        weight[:] = weight + grad * self.rescale_grad
        state[:] = weight


class Updater:
    """Applies an optimizer to indexed weights (reference optimizer.py Updater)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state_multi_precision(index, weight)
            self.states_synced[index] = True
        self.optimizer.update_multi_precision(index, weight, grad, self.states[index])

    def set_states(self, states):
        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            states, self.optimizer = states

        def _nd(s):
            if s is None:
                return None
            if isinstance(s, np.ndarray):
                from .ndarray import array as nd_array
                return nd_array(s, dtype=s.dtype)
            if isinstance(s, (tuple, list)):
                return tuple(_nd(x) for x in s)
            return s
        self.states = {k: _nd(v) for k, v in states.items()}
        self.states_synced = dict.fromkeys(self.states.keys(), True)

    def get_states(self, dump_optimizer=False):
        def _np(s):
            if s is None:
                return None
            if isinstance(s, NDArray):
                return s.asnumpy()
            if isinstance(s, (tuple, list)):
                return tuple(_np(x) for x in s)
            return s
        states = {k: _np(v) for k, v in self.states.items()}
        return pickle.dumps((states, self.optimizer) if dump_optimizer else states)


def get_updater(optimizer):
    return Updater(optimizer)


def create(name, **kwargs):
    if isinstance(name, Optimizer):
        return name
    return Optimizer.create_optimizer(name, **kwargs)


def _on_accelerator(weights):
    """True when the params live on a non-CPU backend (donation there is
    real in-place reuse; on CPU it's unsupported and just warns)."""
    try:
        dev = next(iter(weights[0]._data.devices()))
        return dev.platform != "cpu"
    except Exception as exc:
        # un-probe-able placement degrades to the safe no-donation
        # answer; counted so a donation regression is explainable
        from . import telemetry
        telemetry.swallowed("optimizer.on_accelerator", exc)
        return False


class FusedApplier:
    """Apply an optimizer to MANY parameters in ONE compiled dispatch.

    Eager per-parameter updates cost one host->device dispatch each — for
    a ResNet-50 that is ~160 dispatches per step, which dominates step
    time whenever dispatch latency is nontrivial (the reference amortizes
    the same cost by running updates inside engine bulk segments,
    graph_executor.cc:1377).

    This wrapper traces the SAME registered update ops
    (`ops/optimizer_ops.py`) over every parameter inside a single jitted
    function. Per-step scalars (lr after scheduler/bias-correction, wd,
    rescale_grad) enter as traced inputs so nothing retraces as they
    change. Supported: SGD (fp32, +momentum), Adam; callers fall back to
    per-parameter updates otherwise.

    States are shared with the wrapped `Updater`, so optimizer-state
    save/load round-trips unchanged.
    """

    def __init__(self, updater):
        from .ops.registry import get_op
        self.updater = updater
        self.optimizer = updater.optimizer
        self._get_op = get_op
        self._jit_cache = {}

    @staticmethod
    def supports(optimizer):
        return type(optimizer) in (SGD, Adam) \
            and not getattr(optimizer, "multi_precision", False)

    @classmethod
    def resolve(cls, updater):
        """FusedApplier for the updater's optimizer, or False when the
        per-parameter path must be used. The single resolution point for
        every caller caching a `_fused` attribute."""
        if isinstance(updater, Updater) and cls.supports(updater.optimizer):
            return cls(updater)
        return False

    def _op_name(self):
        if isinstance(self.optimizer, Adam):
            return "adam_update"
        return "sgd_mom_update" if self.optimizer.momentum != 0.0 \
            else "sgd_update"

    def prepare(self, indices, weights):
        """Host-side bookkeeping for one fused update over `indices`:
        create missing states, bump update counts, and return the traced
        per-step inputs (lrs, wds, rescale, state_vals)."""
        import numpy as _np

        opt = self.optimizer
        upd = self.updater
        # host-side bookkeeping identical to Updater.__call__
        for i, w in zip(indices, weights):
            if i not in upd.states:
                upd.states[i] = opt.create_state_multi_precision(i, w)
                upd.states_synced[i] = True
            opt._update_count(i)

        lrs, wds = [], []
        for i in indices:
            lr = opt._get_lr(i)
            if isinstance(opt, Adam):
                t = opt._index_update_count[i]
                lr = lr * math.sqrt(1.0 - opt.beta2 ** t) \
                    / (1.0 - opt.beta1 ** t)
            lrs.append(lr)
            wds.append(opt._get_wd(i))
        # keep the hyperparameter vectors in host numpy: they are weakly
        # committed, so the jitted update runs on the params' device; a
        # jnp.asarray would commit them to the default device and pull the
        # whole fused update across devices on remote-TPU platforms
        lrs = _np.asarray(lrs, _np.float32)
        wds = _np.asarray(wds, _np.float32)
        rescale = _np.float32(opt.rescale_grad)

        state_vals = []
        for i in indices:
            s = upd.states[i]
            if s is None:
                state_vals.append(())
            elif isinstance(s, tuple):
                state_vals.append(tuple(x._data for x in s))
            else:
                state_vals.append((s._data,))
        return lrs, wds, rescale, state_vals

    def update_op(self):
        """(fcompute, static attrs) of the registered optimizer op — the
        building block shared by __call__ and externally fused programs
        (Module's one-dispatch train step)."""
        opt = self.optimizer
        op_name = self._op_name()
        op = self._get_op(op_name)
        static = {"clip_gradient": opt.clip_gradient or -1.0}
        if op_name == "sgd_mom_update":
            static["momentum"] = opt.momentum
        if op_name == "adam_update":
            static.update(beta1=opt.beta1, beta2=opt.beta2,
                          epsilon=opt.epsilon)
        return op_name, op.fcompute, static

    def commit_states(self, indices, new_states):
        """Rebind the updater's state NDArrays to the buffers a fused
        program returned (the states were donated into it)."""
        upd = self.updater
        for i, ns in zip(indices, new_states):
            s = upd.states[i]
            if s is None:
                continue
            if isinstance(s, tuple):
                for old, new in zip(s, ns):
                    old._data = new
            else:
                s._data = ns[0]

    def __call__(self, indices, weights, grads):
        import jax

        devs = {getattr(w._data, "device", None) for w in weights}
        if len(devs) > 1:
            # group2ctx model parallelism keeps each group's parameters on
            # its own device: run one fused apply per device group (the
            # reference's per-array optimizer kernels likewise run on the
            # owning device)
            by_dev = {}
            for i, w, g in zip(indices, weights, grads):
                by_dev.setdefault(getattr(w._data, "device", None),
                                  []).append((i, w, g))
            for items in by_dev.values():
                self([i for i, _, _ in items], [w for _, w, _ in items],
                     [g for _, _, g in items])
            return

        lrs, wds, rescale, state_vals = self.prepare(indices, weights)
        op_name, fcompute, static = self.update_op()

        w_vals = [w._data for w in weights]
        g_vals = [g._data for g in grads]

        donate_key = _on_accelerator(weights)
        key = (op_name, tuple(static.items()), donate_key,
               tuple((v.shape, str(v.dtype)) for v in w_vals))
        fn = self._jit_cache.get(key)
        if fn is None:
            def apply_all(lrs, wds, rescale, ws, gs, states):
                new_ws, new_states = [], []
                # mxanalyze: allow(dispatch-amplification): ws carries heterogeneous shapes (one group per shape is the caller's job); the unroll compiles into ONE fused apply program
                for k in range(len(ws)):
                    params = dict(static)
                    params["lr"] = lrs[k]
                    params["wd"] = wds[k]
                    params["rescale_grad"] = rescale
                    outs = fcompute(params, ws[k], gs[k], *states[k])
                    new_ws.append(outs[0])
                    new_states.append(tuple(outs[1:]))
                return new_ws, new_states

            # donate the optimizer states (adam m/v, momentum): they are
            # internal to the Updater and rebound to the returned buffers
            # below, so XLA updates them in place (the reference's
            # kWriteInplace optimizer kernels). Weights are NOT donated —
            # user code may hold views of the old weight buffers, which
            # donation would invalidate. donate_argnums_for is the
            # repo-wide donation policy point: it strips the set on CPU
            # backends (which don't implement donation).
            from .compiled import donate_argnums_for
            donate = donate_argnums_for(
                weights[0].context, (5,)) if donate_key else ()
            fn = jax.jit(apply_all, donate_argnums=donate)
            self._jit_cache[key] = fn

        new_ws, new_states = fn(lrs, wds, rescale, w_vals, g_vals,
                                state_vals)
        for w, nv in zip(weights, new_ws):
            w._data = nv
        self.commit_states(indices, new_states)
