"""Execution-engine semantics layer.

The reference's threaded dependency engine (`src/engine/threaded_engine.cc`,
`include/mxnet/engine.h:96`) schedules async closures over read/write variable
sets. On TPU, XLA/PJRT already gives us async dispatch with data-flow ordering:
every op launch returns immediately with a future-backed buffer, and
dependencies are carried by the buffers themselves. This module keeps the
*semantics* the reference exposes to users:

- ``waitall()``  == Engine::WaitForAll (`engine.h:219`)
- per-array ``wait_to_read`` == Engine::WaitForVar (`engine.h:213`)
- a serial debug mode == NaiveEngine (`src/engine/naive_engine.cc:36`),
  selected with ``MXNET_ENGINE_TYPE=NaiveEngine`` like the reference
  (`src/engine/engine.cc:32-33`).
- bulking knobs exist as no-ops (XLA fuses within a jitted program already).

Async exceptions: XLA raises device errors at synchronisation points, which
matches the reference's capture-and-rethrow-at-WaitForVar design
(`src/engine/threaded_engine.h:369`).
"""
from __future__ import annotations

import os

import jax

__all__ = ["waitall", "is_naive", "set_engine_type", "fence"]

_ENGINE_TYPE = os.environ.get("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice")


def set_engine_type(name):
    """'NaiveEngine' => every op blocks until complete (serial debugging)."""
    global _ENGINE_TYPE
    _ENGINE_TYPE = name


def is_naive():
    return _ENGINE_TYPE == "NaiveEngine"


def fence(arrs):
    """Wait for every array in ``arrs``. ``block_until_ready`` is the
    barrier: on an attached chip it returns when the buffer is computed
    (chip_smoke.py's device phase times it against a long program).
    Device errors surface at this wait — the reference rethrows async
    exceptions at WaitForVar/WaitForAll the same way."""
    from . import threadsan
    if threadsan.ARMED:   # one attribute read when off
        threadsan.note_dispatch("engine.fence", kind="sync")
    for a in arrs:
        try:
            jax.block_until_ready(a)
        except RuntimeError:
            # a buffer donated between the live_arrays() listing and the
            # wait is gone, not failed; anything else is a device error
            if not a.is_deleted():
                raise


def waitall():
    """Block until all dispatched work is complete (Engine::WaitForAll)."""
    try:
        arrs = jax.live_arrays()
    # mxanalyze: allow(swallowed-exception): a backend torn down at exit has no live arrays to fence — waitall degrades to a no-op
    except Exception:  # pragma: no cover
        arrs = []
    fence(arrs)


def maybe_sync(value):
    """NaiveEngine mode: force completion of a freshly dispatched op."""
    if is_naive():
        jax.block_until_ready(value)
    return value


class BulkScope:
    """Reference `Engine::bulk` / MXNET_EXEC_BULK_EXEC_*: under XLA, bulking
    is jit-compilation; this scope exists for API parity and is a no-op."""

    def __init__(self, size=15):
        self.size = size

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def bulk(size=15):
    return BulkScope(size)
