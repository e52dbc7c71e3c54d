"""Device contexts.

Parity with reference `include/mxnet/base.h:133-264` (`Context`) and
`python/mxnet/context.py`. The TPU-native stack adds ``tpu(i)`` as the
first-class accelerator context; ``gpu(i)`` is kept as an API-compatible alias
that resolves to the platform accelerator so reference user code
(``ctx=mx.gpu(0)``) runs unchanged on TPU hosts.

A Context maps onto a concrete ``jax.Device``; :meth:`Context.jax_device`
states the rule. In CPU mode (``JAX_PLATFORMS=cpu``, as the tests run, with
``--xla_force_host_platform_device_count=N``) the accelerator contexts
resolve onto the virtual host devices so the full test suite runs without a
chip.
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_gpus", "num_tpus"]


class Context:
    """Device context, usable as `with ctx:` scope like the reference."""

    # reference devtype ids (base.h:133+): cpu=1, gpu=2, cpu_pinned=3, cpu_shared=5.
    # tpu=6 is new.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- JAX mapping ------------------------------------------------------
    def jax_device(self) -> "jax.Device":
        """Resolve to a concrete jax.Device.

        cpu contexts resolve to a host device whatever the default
        backend is. tpu/gpu contexts resolve to local accelerator
        ``device_id`` of the default backend, and a ``device_id`` beyond
        the local count is an :class:`MXNetError`: a context never
        lands on another chip than the one it names. The one exception
        is CPU mode, when the default backend itself is ``cpu`` (the
        tests' ``JAX_PLATFORMS=cpu``): accelerator contexts are then
        emulated on the host devices, ``device_id`` wrapping over them.
        """
        # local_devices only: under jax.distributed, jax.devices() is the
        # GLOBAL list and would resolve to another process's
        # (non-addressable) device
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _local_cpu_devices()
            return devs[min(self.device_id, len(devs) - 1)]
        devs = _accelerator_devices()
        if not devs:   # CPU mode
            devs = _local_cpu_devices()
            return devs[self.device_id % len(devs)]
        if self.device_id >= len(devs):
            raise MXNetError(
                "%s: this process has %d accelerator device(s) (%s)"
                % (self, len(devs), devs[0].device_kind))
        return devs[self.device_id]

    def empty_cache(self):
        """Reference `Context.empty_cache`; XLA manages its own pools: no-op."""


def _accelerator_devices():
    """This process's devices of the default backend, ``[]`` when that
    backend is ``cpu``. A backend that fails to initialize raises."""
    return [d for d in jax.local_devices() if d.platform != "cpu"]


def _local_cpu_devices():
    """This process's cpu devices. The default backend may be an
    accelerator, so query the cpu backend explicitly — never the global
    jax.devices('cpu') list, whose head belongs to process 0."""
    try:
        return jax.local_devices(backend="cpu")
    except RuntimeError as exc:
        # the platform list named the accelerator alone: jax creates only
        # the backends it names. The default context is cpu(0) and
        # iterators, loaded params and metrics live on the host, so the
        # host backend has to exist beside the chip's
        raise MXNetError(
            "cpu contexts need jax's cpu backend; add it to the platform "
            "list (JAX_PLATFORMS=%s,cpu)" % jax.default_backend()) from exc


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """API-compat alias: resolves onto the platform accelerator (TPU)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_gpus():
    """Reference `mx.context.num_gpus`; counts attached accelerator chips."""
    return len(_accelerator_devices())


def num_tpus():
    return len(_accelerator_devices())


def current_context():
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value
