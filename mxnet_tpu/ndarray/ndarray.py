"""NDArray: the imperative tensor.

Parity with reference `include/mxnet/ndarray.h:82` and
`python/mxnet/ndarray/ndarray.py`. TPU-native design: an NDArray wraps a
``jax.Array`` (a PJRT device buffer). The reference's engine-variable
machinery (each NDArray owning an engine var; ops declaring read/write sets,
`ndarray.h` WaitToRead/WaitToWrite) is subsumed by XLA's async dispatch —
every op returns a future-backed buffer and ordering is data-flow. In-place
mutation (`kWriteInplace`/`kAddTo`, `a[:]=`, `+=`) is realised functionally:
the wrapper rebinds its buffer, preserving reference semantics at the Python
API while staying pure underneath (XLA donates/reuses buffers).

The payload may also be a JAX tracer: the same NDArray code then serves as
the symbolic tracing path for hybridize/Executor (reference CachedOp,
`src/imperative/cached_op.cc:342`).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..base import MXNetError, dtype_np, numeric_types, integer_types, \
    device_of
from ..context import Context, current_context, cpu
from ..ops.invoke import invoke

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange",
           "concatenate", "moveaxis", "waitall", "imdecode"]


def _is_tracer(v):
    return isinstance(v, jax.core.Tracer)


class NDArray:
    """A device tensor with reference-compatible imperative semantics."""

    __slots__ = ("_data", "_ctx", "_autograd_node", "_requires_grad",
                 "_grad_req", "grad", "_writable", "__weakref__")
    # make numpy defer to NDArray.__r<op>__
    __array_priority__ = 1000.0

    def __init__(self, data, ctx=None):
        self._data = data
        self._ctx = ctx if ctx is not None else current_context()
        self._autograd_node = None
        self._requires_grad = False
        self._grad_req = "null"
        self.grad = None
        self._writable = True

    # ------------------------------------------------------------------
    # properties
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def size(self):
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def dtype(self):
        return np.dtype(self._data.dtype)

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return "default"

    @property
    def T(self):
        return self.transpose()

    # ------------------------------------------------------------------
    # sync / conversion (reference WaitToRead + SyncCopyToCPU)
    # ------------------------------------------------------------------
    def wait_to_read(self):
        if not _is_tracer(self._data):
            from .. import engine
            engine.fence([self._data])

    wait_to_write = wait_to_read

    def asnumpy(self):
        if _is_tracer(self._data):
            raise MXNetError("cannot convert symbolic/traced NDArray to numpy")
        return np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("The truth value of an NDArray with multiple elements "
                         "is ambiguous.")

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def astype(self, dtype, copy=True):
        dtype = dtype_np(dtype)
        if not copy and dtype == self.dtype:
            return self
        return invoke("Cast", [self], {"dtype": dtype})

    def copy(self):
        return invoke("_copy", [self])

    def copyto(self, other):
        """Reference `CopyFromTo` (src/ndarray/ndarray.cc:1060)."""
        if isinstance(other, NDArray):
            if other.shape != self.shape:
                raise MXNetError("copyto: shape mismatch %s vs %s"
                                 % (self.shape, other.shape))
            # into the destination's own placement: a replicated or
            # sharded buffer of an SPMD module stays on its mesh
            place = device_of(other._data)
            if place is None:
                place = other.ctx.jax_device()
            other._data = jax.device_put(self._data, place).astype(other.dtype)
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device()), other)
        raise TypeError("copyto does not support type " + str(type(other)))

    def as_in_context(self, context):
        if self.ctx == context:
            return self
        return self.copyto(context)

    def detach(self):
        out = NDArray(self._data, self._ctx)
        return out

    def attach_grad(self, grad_req="write", stype=None):
        """Reference gluon Parameter/autograd leaf marking."""
        self._requires_grad = True
        self._grad_req = grad_req
        self.grad = NDArray(jnp.zeros(self.shape, self.dtype,
                                      device=device_of(self._data)),
                            self._ctx)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def __getitem__(self, key):
        key = _normalize_index(key)
        out = self._data[key]
        return NDArray(out, self._ctx)

    def __setitem__(self, key, value):
        if not self._writable:
            raise MXNetError("trying to write to a readonly NDArray")
        key = _normalize_index(key)
        if isinstance(value, NDArray):
            value = value._data
        elif isinstance(value, (np.ndarray, list, tuple, *numeric_types)):
            # keep host constants in numpy: they are weakly committed, so
            # the .at[].set below runs on self's device instead of pulling
            # everything through the default device
            value = np.asarray(value, dtype=self.dtype)
        if key == slice(None) and getattr(value, "shape", None) == self.shape:
            if isinstance(value, np.ndarray):
                self._data = jax.device_put(value, device_of(self._data))
            else:
                # a device-array source must land on SELF's device — binding
                # the source buffer directly would silently migrate this
                # array to the source's device (caught by the TPU lane:
                # Module._load_batch feeding a cpu batch into a tpu executor)
                new = jnp.asarray(value, self.dtype)
                dev = device_of(self._data)
                if dev is not None and device_of(new) not in (None, dev):
                    new = jax.device_put(new, dev)
                self._data = new
        else:
            dev = device_of(self._data)
            new = self._data.at[key].set(value.astype(self.dtype)
                                         if hasattr(value, "astype") else value)
            # scatter results may come back with a different placement
            # than self (the compiler can pick replicated for a small
            # mesh-sharded operand): an in-place write must never move
            # this array off its committed device/sharding
            if dev is not None and device_of(new) != dev:
                new = jax.device_put(new, dev)
            self._data = new

    def slice_assign(self, rhs, begin, end, step=None):
        key = tuple(slice(b, e, s) for b, e, s in
                    zip(begin, end, step or [None] * len(begin)))
        self[key] = rhs
        return self

    # ------------------------------------------------------------------
    # shape ops (delegate to registered operators)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        if not shape:
            shape = kwargs.get("shape", ())
        return invoke("Reshape", [self], {"shape": tuple(shape)})

    def reshape_like(self, other):
        return invoke("reshape_like", [self, other])

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return invoke("transpose", [self], {"axes": axes or None})

    def swapaxes(self, dim1, dim2):
        return invoke("SwapAxis", [self], {"dim1": dim1, "dim2": dim2})

    def flatten(self):
        return invoke("Flatten", [self])

    def expand_dims(self, axis):
        return invoke("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None):
        return invoke("squeeze", [self], {"axis": axis})

    def broadcast_to(self, shape):
        return invoke("broadcast_to", [self], {"shape": tuple(shape)})

    def broadcast_like(self, other):
        return invoke("broadcast_like", [self, other])

    def tile(self, reps):
        return invoke("tile", [self], {"reps": tuple(reps) if isinstance(reps, (list, tuple)) else (reps,)})

    def repeat(self, repeats, axis=None):
        return invoke("repeat", [self], {"repeats": repeats, "axis": axis})

    def pad(self, mode, pad_width, constant_value=0):
        return invoke("Pad", [self], {"mode": mode, "pad_width": pad_width,
                                      "constant_value": constant_value})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke("SliceChannel", [self],
                      {"num_outputs": num_outputs, "axis": axis,
                       "squeeze_axis": squeeze_axis})

    def slice(self, begin, end, step=None):
        return invoke("slice", [self], {"begin": begin, "end": end, "step": step})

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", [self, indices], {"axis": axis, "mode": mode})

    def pick(self, index, axis=-1, keepdims=False):
        return invoke("pick", [self, index], {"axis": axis, "keepdims": keepdims})

    def one_hot(self, depth, on_value=1.0, off_value=0.0, dtype="float32"):
        return invoke("one_hot", [self], {"depth": depth, "on_value": on_value,
                                          "off_value": off_value, "dtype": dtype})

    def clip(self, a_min, a_max):
        return invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def abs(self):
        return invoke("abs", [self])

    def sign(self):
        return invoke("sign", [self])

    def flip(self, axis):
        return invoke("flip", [self], {"axis": axis})

    def diag(self, k=0):
        return invoke("diag", [self], {"k": k})

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke("norm", [self], {"ord": ord, "axis": axis, "keepdims": keepdims})

    def tostype(self, stype):
        from . import sparse
        return sparse.cast_storage(self, stype)

    def as_np(self):
        return self._data

    # reductions -------------------------------------------------------
    def sum(self, axis=None, keepdims=False, **kw):
        return invoke("sum", [self], {"axis": axis, "keepdims": keepdims, **kw})

    def nansum(self, axis=None, keepdims=False, **kw):
        return invoke("nansum", [self], {"axis": axis, "keepdims": keepdims, **kw})

    def mean(self, axis=None, keepdims=False, **kw):
        return invoke("mean", [self], {"axis": axis, "keepdims": keepdims, **kw})

    def max(self, axis=None, keepdims=False, **kw):
        return invoke("max", [self], {"axis": axis, "keepdims": keepdims, **kw})

    def min(self, axis=None, keepdims=False, **kw):
        return invoke("min", [self], {"axis": axis, "keepdims": keepdims, **kw})

    def prod(self, axis=None, keepdims=False, **kw):
        return invoke("prod", [self], {"axis": axis, "keepdims": keepdims, **kw})

    def argmax(self, axis=None, keepdims=False):
        return invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def argsort(self, axis=-1, is_ascend=True):
        return invoke("argsort", [self], {"axis": axis, "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True):
        return invoke("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return invoke("topk", [self], {"axis": axis, "k": k, "ret_typ": ret_typ,
                                       "is_ascend": is_ascend})

    def dot(self, other, **kw):
        return invoke("dot", [self, other], kw)

    def __matmul__(self, other):
        return invoke("dot", [self, other], {})

    def square(self):
        return invoke("square", [self])

    def sqrt(self):
        return invoke("sqrt", [self])

    def exp(self):
        return invoke("exp", [self])

    def log(self):
        return invoke("log", [self])

    def relu(self):
        return invoke("relu", [self])

    def sigmoid(self):
        return invoke("sigmoid", [self])

    def tanh(self):
        return invoke("tanh", [self])

    def softmax(self, axis=-1, **kw):
        return invoke("softmax", [self], {"axis": axis, **kw})

    def log_softmax(self, axis=-1, **kw):
        return invoke("log_softmax", [self], {"axis": axis, **kw})

    # ------------------------------------------------------------------
    # arithmetic
    # ------------------------------------------------------------------
    def __add__(self, other):
        return _binary(self, other, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return _binary(self, other, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, other):
        return _binary(self, other, "broadcast_sub", "_rminus_scalar", reverse=True)

    def __mul__(self, other):
        return _binary(self, other, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return _binary(self, other, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, other):
        return _binary(self, other, "broadcast_div", "_rdiv_scalar", reverse=True)

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __mod__(self, other):
        return _binary(self, other, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, other):
        return _binary(self, other, "broadcast_mod", "_rmod_scalar", reverse=True)

    def __pow__(self, other):
        return _binary(self, other, "broadcast_power", "_power_scalar")

    def __rpow__(self, other):
        return _binary(self, other, "broadcast_power", "_rpower_scalar", reverse=True)

    def __neg__(self):
        return invoke("negative", [self])

    def __abs__(self):
        return invoke("abs", [self])

    def __iadd__(self, other):
        res = self.__add__(other)
        self._data = res._data
        self._autograd_node = res._autograd_node
        return self

    def __isub__(self, other):
        res = self.__sub__(other)
        self._data = res._data
        self._autograd_node = res._autograd_node
        return self

    def __imul__(self, other):
        res = self.__mul__(other)
        self._data = res._data
        self._autograd_node = res._autograd_node
        return self

    def __itruediv__(self, other):
        res = self.__truediv__(other)
        self._data = res._data
        self._autograd_node = res._autograd_node
        return self

    __idiv__ = __itruediv__

    # comparisons ------------------------------------------------------
    def __eq__(self, other):
        return _binary(self, other, "broadcast_equal", "_equal_scalar")

    def __ne__(self, other):
        return _binary(self, other, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, other):
        return _binary(self, other, "broadcast_greater", "_greater_scalar")

    def __ge__(self, other):
        return _binary(self, other, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, other):
        return _binary(self, other, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, other):
        return _binary(self, other, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __repr__(self):
        if _is_tracer(self._data):
            return "<NDArray traced %s %s>" % (self.shape, self.dtype)
        return "\n%s\n<NDArray %s @%s>" % (
            str(self.asnumpy()), "x".join(map(str, self.shape)), self.ctx)

    # dlpack interop (reference 3rdparty/dlpack; here `jax.dlpack`) -----
    def __dlpack__(self, stream=None):
        return self._data.__dlpack__()

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()


def _normalize_index(key):
    if isinstance(key, NDArray):
        return key._data
    if isinstance(key, tuple):
        return tuple(k._data if isinstance(k, NDArray) else k for k in key)
    return key


def _binary(lhs, rhs, op, scalar_op, reverse=False):
    if isinstance(rhs, NDArray):
        return invoke(op, [lhs, rhs])
    if isinstance(rhs, numeric_types):
        return invoke(scalar_op, [lhs], {"scalar": float(rhs)})
    if isinstance(rhs, np.ndarray):
        other = array(rhs, ctx=lhs.ctx)
        # reverse=True means lhs is really the right operand (e.g. np - nd)
        ins = [other, lhs] if reverse else [lhs, other]
        return invoke(op, ins)
    raise TypeError("type %s not supported" % str(type(rhs)))


def _from_data(value, ctx=None):
    return NDArray(value, ctx)


def _wrap_like(value, like):
    return NDArray(value, like.ctx)


# ----------------------------------------------------------------------
# creation functions (reference python/mxnet/ndarray/ndarray.py + utils)
# ----------------------------------------------------------------------
def _dev(ctx):
    ctx = ctx or current_context()
    return ctx, ctx.jax_device()


def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        dtype = dtype or source_array.dtype
        return source_array.astype(dtype).as_in_context(ctx or source_array.ctx)
    npa = np.asarray(source_array, dtype=dtype_np(dtype) if dtype is not None
                     else None)
    if npa.dtype == np.float64 and dtype is None:
        npa = npa.astype(np.float32)
    if npa.dtype == np.int64 and dtype is None and not isinstance(source_array, np.ndarray):
        npa = npa.astype(np.int32) if npa.size and np.abs(npa).max() < 2**31 else npa
    ctx, dev = _dev(ctx)
    # single host->dev put; routing through jnp.asarray first would
    # materialize on the DEFAULT device (the chip, on a TPU host) before
    # transferring
    return NDArray(jax.device_put(npa, dev), ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    ctx, dev = _dev(ctx)
    shape = (shape,) if isinstance(shape, integer_types) else tuple(shape)
    return NDArray(jnp.zeros(shape, dtype_np(dtype), device=dev), ctx)


def ones(shape, ctx=None, dtype=None, **kwargs):
    ctx, dev = _dev(ctx)
    shape = (shape,) if isinstance(shape, integer_types) else tuple(shape)
    return NDArray(jnp.ones(shape, dtype_np(dtype), device=dev), ctx)


def full(shape, val, ctx=None, dtype=None):
    ctx, dev = _dev(ctx)
    shape = (shape,) if isinstance(shape, integer_types) else tuple(shape)
    return NDArray(jnp.full(shape, val, dtype_np(dtype), device=dev), ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    ctx, dev = _dev(ctx)
    out = jnp.arange(start, stop, step, dtype_np(dtype), device=dev)
    if repeat > 1:
        out = jnp.repeat(out, repeat)
    return NDArray(out, ctx)


def concatenate(arrays, axis=0, always_copy=True):
    vals = [a._data for a in arrays]
    return NDArray(jnp.concatenate(vals, axis=axis), arrays[0].ctx)


def moveaxis(tensor, source, destination):
    return NDArray(jnp.moveaxis(tensor._data, source, destination), tensor.ctx)


def waitall():
    from .. import engine
    engine.waitall()


def imdecode(str_img, clip_rect=(0, 0, 0, 0), out=None, index=0, channels=3, mean=None):
    raise NotImplementedError("use mxnet_tpu.image.imdecode")
