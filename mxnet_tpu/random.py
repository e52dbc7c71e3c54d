"""Random number state.

Parity with reference `src/common/random_generator.h` + `python/mxnet/random.py`.
TPU-native: a counter-based threefry key (JAX PRNG) replaces the per-device
mshadow RNG; `seed()` resets every stream. Sampling ops split a fresh subkey
per call, so eager sampling is stateful at the API while each op stays pure
(SURVEY.md §7 hard-part 7: bitwise parity with the reference RNG is
deliberately not attempted; tests are statistical).

Like the reference (one sampler per device, random_generator.h), the key
chain is **per jax.Device**: splits execute on the device that will consume
the bits. A single global key would live on the default device and drag
every op on another device through a cross-device copy per sample.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["seed", "next_key"]


class _RandState(threading.local):
    # key creation is lazy: touching the PRNG at import time would
    # initialise the XLA backend before jax.distributed.initialize can run
    # (multi-process workers must import the package first)
    def __init__(self):
        super().__init__()
        self.seed_val = 0
        self.dev_seeds = {}     # jax.Device -> pending per-device seed
        self.keys = {}          # jax.Device -> current chain key
        self.override = None

    def key_for(self, dev):
        dev = _normalize_dev(dev)
        key = self.keys.get(dev)
        if key is None:
            key = jax.random.PRNGKey(self.dev_seeds.get(dev, self.seed_val))
            if dev is not None:
                if hasattr(dev, "device_set"):
                    # SPMD executor: the replicated chain's stream matches
                    # the lead device's single-device chain, so an
                    # N-device run reproduces the 1-device trajectory
                    lead = min(dev.device_set, key=lambda d: d.id)
                    key = jax.random.fold_in(key, lead.id)
                    key = jax.device_put(key, dev)
                else:
                    key = jax.device_put(key, dev)
                    # decorrelate streams across devices (reference seeds
                    # each device sampler with seed ^ devid,
                    # random_generator.h)
                    key = jax.random.fold_in(key, dev.id)
            self.keys[dev] = key
        return key


_STATE = _RandState()


def _normalize_dev(dev):
    """Key-chain identity for a placement: a Sharding is normalized to
    the REPLICATED sharding over its mesh — a (2,) key can never carry a
    sharded spec (an fsdp/tensor param used as the placement anchor
    would otherwise try to split the key across devices), and all
    anchors over one mesh share a single chain. EVERY chain read/write
    must go through this, or a sharded anchor would read one cache entry
    and advance another (a frozen key chain)."""
    if hasattr(dev, "device_set"):
        mesh = getattr(dev, "mesh", None)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            return NamedSharding(mesh, PartitionSpec())
    return dev


def _resolve_device(ctx):
    """ctx may be a Context, a jax.Device, or None (current context)."""
    if ctx is None or ctx == "all":
        from .context import current_context
        ctx = current_context()
    if hasattr(ctx, "jax_device"):
        try:
            return ctx.jax_device()
        except Exception as exc:
            # a context without a live backing device resolves to None
            # (callers fall back to the default chain) — counted, so a
            # systematically unresolvable device is visible
            from . import telemetry
            telemetry.swallowed("random.resolve_device", exc)
            return None
    return ctx


def seed(seed_state, ctx="all"):
    """Reset the key chains (reference mx.random.seed: reseeds every
    device's sampler when ctx='all', one device otherwise). Also reseeds
    granted RNG resources (reference ResourceManager::SeedRandom,
    src/resource.cc)."""
    seed_state = int(seed_state)
    if ctx == "all":
        _STATE.seed_val = seed_state
        _STATE.dev_seeds.clear()
        _STATE.keys.clear()
    else:
        # scope the reseed to one device: lazily-initialized devices keep
        # deriving from the previous global seed
        dev = _resolve_device(ctx)
        _STATE.dev_seeds[dev] = seed_state
        _STATE.keys.pop(dev, None)
    from . import resource as _resource
    _resource._manager.seed_all(seed_state, ctx)


def _split_chain(dev):
    """Advance dev's key chain, returning a fresh subkey."""
    dev = _normalize_dev(dev)  # same identity key_for cached under
    key = _STATE.key_for(dev)
    _STATE.keys[dev], sub = jax.random.split(key)
    return sub


def next_key(ctx=None):
    """Fresh subkey on ctx's device. Inside a traced scope (see key_scope)
    the key chain derives from the scope's (possibly tracer) key so compiled
    programs get a per-call key argument instead of a baked constant."""
    if _STATE.override is not None:
        _STATE.override, sub = jax.random.split(_STATE.override)
        return sub
    return _split_chain(_resolve_device(ctx))


def next_key_like(val):
    """Fresh subkey on the device holding `val` (a jax.Array) — the path
    compiled callers use so the key is already co-located with the program's
    arguments."""
    if _STATE.override is not None:
        return next_key()
    from .base import device_of
    return _split_chain(device_of(val))


def get_key(ctx=None):
    return _STATE.key_for(_resolve_device(ctx))


class key_scope:
    """Route next_key() to derive from `key` (used when tracing jitted
    programs that sample — dropout under hybridize)."""

    def __init__(self, key):
        self._key = key
        self._saved = None

    def __enter__(self):
        self._saved = _STATE.override
        _STATE.override = self._key
        return self

    def __exit__(self, *a):
        _STATE.override = self._saved
        return False
