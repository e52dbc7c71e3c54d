"""CompiledProgram: the ONE compiled-program layer of the framework.

Every jit entry point — executor forward / fused fwd+bwd, Module's
fused and scanned train steps, gluon hybridize, the data-parallel front
doors (and, through the executor, the serving replicas) — is a thin
client of this module. A :class:`CompiledProgram` owns, in one place,
everything those five call sites used to reimplement independently:

- the **signature -> executable cache** (abstract shape/dtype/weak-type/
  sharding keys; Python scalars are type-only so per-step hyperparameter
  values can never fake a retrace);
- **AOT warmup**: a cache miss compiles ahead of time
  (``fn.lower(*args).compile()``) and later calls dispatch the compiled
  executable directly; :meth:`warmup` pre-populates a signature without
  executing it (the serving/bench warm-start path);
- **donation decisions**: :func:`donate_argnums_for` is the single
  policy point for "may these buffers be freed by XLA" (accelerators
  donate, CPU backends do not implement donation), replacing the
  per-call-site device_type checks;
- **cost-analysis / ledger hooks**: every compile records its FLOPs
  (``cost_analysis``) and temp/output bytes (``memory_analysis``) into
  `xla_stats`' ledger, its collective inventory (HLO-text parse) into
  `shardprof`'s communication ledger, and the program keeps
  ``last_flops`` / ``last_memory`` for the MFU pipeline
  (`xla_stats.note_train_step`);
- a **sharding policy** slot: a `parallel.spmd.ShardingPolicy` (or any
  object with a ``mesh``) attached at construction makes every
  compile/dispatch run under ``with policy.mesh``, so sharding
  constraints inside the traced function resolve against the named
  mesh, and the policy is introspectable on the program
  (``program.policy``).

Accounting (counters, the retrace explainer, flight-recorder events)
still lands in `mxnet_tpu.xla_stats` / `mxnet_tpu.telemetry` — this
module owns the MACHINERY, xla_stats owns the TELEMETRY. The
back-compat names ``xla_stats.tracked_jit`` / ``xla_stats.TrackedJit``
resolve here; no other module may grow its own signature cache
(asserted by ``tests/test_spmd.py::test_single_compiled_program_layer``).

Lock order: a program's per-instance ``_compile_lock`` may be held when
the module-global ``_lock`` is taken (compile bookkeeping); never the
reverse. Telemetry's registry lock is innermost of all.
"""
from __future__ import annotations

import logging
import os
import threading
import time

from . import telemetry, threadsan

__all__ = ["CompiledProgram", "tracked_jit", "aot_compile",
           "donate_argnums_for", "spmd_donate_enabled",
           "explain_signature_change", "last_retrace", "reset",
           "enable_compile_cache"]

logger = logging.getLogger("mxnet_tpu.compiled")

_lock = threadsan.register("compiled._lock", threading.RLock())
_sites = {}    # (site, lineage) -> {"compiles": int, "sig": dict or None}
_state = {"last_retrace": None}

#: device_type values donation is skipped for: CPU backends do not
#: implement buffer donation (JAX warns per compile and ignores it)
_NO_DONATE_DEVICE_TYPES = ("cpu", "cpu_pinned", "cpu_shared")


#: where the persistent compile cache lives when the environment does
#: not place it: one fixed directory inside the checkout, because the
#: path is part of the cache's key — a directory that moves never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache():
    """Turn on jax's persistent compilation cache and return its
    directory. For entry scripts (chip_smoke.py, bench.py, the serving
    CLI, the example benchmarks) to call before their first compile —
    never at ``import mxnet_tpu``, so the tests run without it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    this sets nothing; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def _enabled():
    return os.environ.get("MXNET_XLA_STATS", "1") != "0"


def _aot_enabled():
    return os.environ.get("MXNET_XLA_STATS_AOT", "1") != "0"


def reset():
    """Drop per-site compile state (tests). Pair with
    ``telemetry.reset()``/``xla_stats.reset()``."""
    with _lock:
        _sites.clear()
        _state["last_retrace"] = None


def last_retrace():
    """Metadata of the most recent retrace: ``{"site", "reason",
    "compiles", "time"}`` or None."""
    with _lock:
        return dict(_state["last_retrace"]) if _state["last_retrace"] \
            else None


def spmd_donate_enabled():
    """Whether SPMD policies may UNLOCK param-buffer donation
    (``MXNET_SPMD_DONATE``, default on). Scopes the opt-out to the
    donations SPMD added — the legacy non-SPMD optimizer-state donation
    predates the knob and must not be stripped by it."""
    return os.environ.get("MXNET_SPMD_DONATE", "1") != "0"


def donate_argnums_for(ctx, argnums):
    """The donation decision for a compiled step on ``ctx`` (a Context,
    a jax Device, or None): ``argnums`` on accelerators, ``()`` on CPU
    backends (which do not implement donation — JAX would warn per
    compile)."""
    kind = getattr(ctx, "device_type", None)
    if kind is None:   # a jax Device (or None -> default backend)
        kind = getattr(ctx, "platform", None)
        if kind is None and ctx is None:
            try:
                import jax
                kind = jax.devices()[0].platform
            except Exception as exc:
                telemetry.swallowed("compiled.donate_argnums_for", exc)
                kind = "cpu"
    return () if str(kind) in _NO_DONATE_DEVICE_TYPES \
        else tuple(argnums)


# ---------------------------------------------------------------------------
# Abstract signatures: fast hashable keys + printable descriptions
# ---------------------------------------------------------------------------

def _describe_leaf(x):
    """Hashable description of one argument leaf. Array-likes are
    abstracted to (shape, dtype, weak_type, sharding) — values never
    enter, so hyperparameters that change per step cannot fake a
    retrace. Python scalars are type-only (jit traces them)."""
    if x is None:
        return ("none",)
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        weak = bool(getattr(getattr(x, "aval", None), "weak_type", False))
        sharding = getattr(x, "sharding", None)
        return ("array", tuple(shape), str(dtype), weak, sharding)
    if isinstance(x, (bool, int, float, complex, str, bytes)):
        return ("scalar", type(x).__name__)
    return ("opaque", type(x).__name__)


def _key_leaf(x):
    """Per-call fast variant of :func:`_describe_leaf`: same abstraction
    but keeps dtype/sharding as hashable OBJECTS (str(dtype) alone costs
    ~6us a leaf, which dominates dispatch at ResNet parameter counts)."""
    if x is None:
        return ("none",)
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        aval = getattr(x, "aval", None)
        weak = aval.weak_type if aval is not None else False
        return ("array", tuple(shape), dtype, weak,
                getattr(x, "sharding", None))
    if isinstance(x, (bool, int, float, complex, str, bytes)):
        return ("scalar", type(x).__name__)
    return ("opaque", type(x).__name__)


def _key_of(obj):
    if isinstance(obj, dict):
        try:
            items = sorted(obj.items())
        except TypeError:   # mixed/unorderable keys
            items = sorted(obj.items(), key=lambda kv: str(kv[0]))
        return ("d",) + tuple((k, _key_of(v)) for k, v in items)
    if isinstance(obj, (list, tuple)):
        return ("t",) + tuple(_key_of(v) for v in obj)
    return _key_leaf(obj)


def _describe_args(args, static):
    """{path: leaf description} over the positional args — built only on
    cache miss, for the retrace explainer."""
    entries = {}

    def walk(prefix, obj):
        if isinstance(obj, dict):
            for k in sorted(obj, key=str):
                walk("%s[%r]" % (prefix, k), obj[k])
        elif isinstance(obj, (list, tuple)):
            for i, v in enumerate(obj):
                walk("%s[%d]" % (prefix, i), v)
        else:
            entries[prefix] = _describe_leaf(obj)

    for i, a in enumerate(args):
        if i in static:
            entries["arg%d(static)" % i] = ("static", repr(a))
        else:
            walk("arg%d" % i, a)
    return entries


def _fmt_desc(d):
    if d[0] == "array":
        out = "shape %s dtype %s" % (tuple(d[1]), d[2])
        if d[3]:
            out += " (weak)"
        return out
    if d[0] == "static":
        return "static %s" % d[1]
    if d[0] == "scalar":
        return "python %s" % d[1]
    return d[0]


def _diff_desc(a, b):
    if a[0] == "array" and b[0] == "array":
        parts = []
        if a[1] != b[1]:
            msg = "shape %s -> %s" % (tuple(a[1]), tuple(b[1]))
            if len(a[1]) == len(b[1]):
                dims = ", ".join("dim %d: %s -> %s" % (i, x, y)
                                 for i, (x, y) in enumerate(zip(a[1], b[1]))
                                 if x != y)
                msg += " (%s)" % dims
            parts.append(msg)
        if a[2] != b[2]:
            parts.append("dtype %s -> %s" % (a[2], b[2]))
        if a[3] != b[3]:
            parts.append("weak_type %s -> %s" % (a[3], b[3]))
        if a[4] != b[4]:
            parts.append("sharding %s -> %s" % (a[4], b[4]))
        return ", ".join(parts) or "changed"
    if a[0] == "static" and b[0] == "static":
        return "static value %s -> %s" % (a[1], b[1])
    return "%s -> %s" % (_fmt_desc(a), _fmt_desc(b))


def explain_signature_change(old, new):
    """Human-readable diff of two ``_describe_args`` signatures: names
    every path whose abstract description changed, down to the dimension
    for rank-preserving shape changes."""
    parts = []
    for k in sorted(set(old) | set(new)):
        a, b = old.get(k), new.get(k)
        if a == b:
            continue
        if a is None:
            parts.append("%s: new input (%s)" % (k, _fmt_desc(b)))
        elif b is None:
            parts.append("%s: input removed (was %s)" % (k, _fmt_desc(a)))
        else:
            parts.append("%s: %s" % (k, _diff_desc(a, b)))
    return "; ".join(parts) or \
        "no signature change detected (new code object or closure)"


# ---------------------------------------------------------------------------
# The compiled-program layer
# ---------------------------------------------------------------------------

def _count(name, site, help=""):
    telemetry.counter(name, help=help).inc()
    telemetry.counter(name, help=help, site=site).inc()


def _memprof_dispatch(site):
    """Memory anatomy hook at dispatch: throttled HBM timeline sample
    plus the ``memory.oom`` chaos poll (an injected error propagates
    into the dispatch OOM handler below). Lazy import like the
    runprof/shardprof hooks; only the import itself is guarded —
    memprof swallows its own internals."""
    try:
        from . import memprof
    except Exception as exc:
        telemetry.swallowed("compiled.memprof", exc)
        return
    memprof.on_dispatch(site)


def _memprof_oom(exc, site):
    """The DeviceOOMError to raise in place of ``exc`` when memprof
    recognizes a RESOURCE_EXHAUSTED (postmortem written as a side
    effect), else None."""
    try:
        from . import memprof
        return memprof.maybe_oom_error(exc, site=site)
    except Exception as exc2:
        telemetry.swallowed("compiled.memprof_oom", exc2)
        return None


def _flops_of(compiled):
    try:
        cost = compiled.cost_analysis()
    except Exception as exc:
        telemetry.swallowed("compiled.cost_analysis", exc)
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    try:
        f = cost.get("flops")
    except AttributeError:
        return None
    # XLA reports negative flops (-1/-2) for computations it cannot
    # cost (callbacks, custom calls): that is "unknown", not a figure
    return float(f) if f is not None and f > 0 else None


def _memory_of(compiled):
    try:
        m = compiled.memory_analysis()
        return {"argument_bytes": int(m.argument_size_in_bytes),
                "output_bytes": int(m.output_size_in_bytes),
                "temp_bytes": int(m.temp_size_in_bytes),
                "code_bytes": int(m.generated_code_size_in_bytes)}
    except Exception as exc:
        telemetry.swallowed("compiled.memory_analysis", exc)
        return None


def _any_tracer(args):
    """Whether any leaf of ``args`` is a jax tracer: the call then sits
    inside an outer trace and must go through plain jit dispatch."""
    import jax
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(args))


def _hashable(x):
    try:
        hash(x)
        return True
    except TypeError:
        return False


class _Entry:
    __slots__ = ("compiled", "flops", "memory")

    def __init__(self, compiled, flops, memory):
        self.compiled = compiled
        self.flops = flops
        self.memory = memory


class CompiledProgram:
    """A ``jax.jit`` owning its signature cache, AOT warmup, donation,
    cost-analysis hooks, and (optionally) a sharding policy — see the
    module docstring.

    A cache miss is a compile (and, beyond the lineage's first, a
    retrace with an explained diff); a hit calls the cached executable.
    Tracer inputs and keyword calls fall through to the plain jit
    dispatch path.

    ``lineage`` scopes retrace detection: wrappers sharing (site,
    lineage) — e.g. the executors a Module rebinds over one Symbol, or
    the rebuilt jits of one gluon block — diff against each other, so a
    reshape-triggered recompile IS reported as a retrace; wrappers with
    different lineages (two unrelated models hitting the same site in
    one process) never cross-diff, and the second model's first compile
    is just a compile. Default: this wrapper instance only.

    ``policy`` (a `parallel.spmd.ShardingPolicy`, or anything with a
    ``mesh`` attribute) makes every trace/compile/dispatch run inside
    ``with policy.mesh`` so sharding constraints in the traced function
    resolve against the named mesh.
    """

    def __init__(self, fun, site, static_argnums=(), lineage=None,
                 policy=None, **jit_kwargs):
        import jax
        if isinstance(static_argnums, int):
            static_argnums = (static_argnums,)
        self.site = site
        self.policy = policy
        self._lineage = (site, lineage if lineage is not None
                         else id(self))
        self._static = frozenset(static_argnums)
        self.donate_argnums = tuple(jit_kwargs.get("donate_argnums") or ())
        # mxanalyze: allow(retrace-hazard): pass-through wrapper — the static set is the caller's literal, linted at the caller's wrap site
        self._fn = jax.jit(fun, static_argnums=tuple(static_argnums),
                           **jit_kwargs)
        self._cache = {}
        # dispatch_ok: this lock EXISTS to serialize compiles, and a
        # compile traces the user fn — which may dispatch a nested
        # CompiledProgram (gluon block inside a fused step). That is the
        # double-checked cache working as designed, not a stall hazard.
        self._compile_lock = threadsan.register(
            "compiled.CompiledProgram._compile_lock", threading.Lock(),
            dispatch_ok=True)
        self.last_flops = None
        self.last_memory = None

    def _mesh_scope(self):
        mesh = getattr(self.policy, "mesh", None)
        if mesh is not None:
            return mesh
        import contextlib
        return contextlib.nullcontext()

    # jax.jit API passthroughs used by callers/tests
    def lower(self, *args, **kwargs):
        with self._mesh_scope():
            return self._fn.lower(*args, **kwargs)

    def compiled_text(self):
        """The optimized HLO text of every executable this program holds:
        the instructions under the names a device trace shows them by, each
        with the ``op_name`` it was traced under (`jax.named_scope`), which
        the trace itself leaves out."""
        return [entry.compiled.as_text() for entry in self._cache.values()
                if entry.compiled is not None]

    def warmup(self, *args):
        """AOT-compile the signature of ``args`` into the cache WITHOUT
        executing the program (serving/bench warm start). Returns self.
        The compile lands in the same counters/ledger as a miss-driven
        compile, so ``compile_counts()`` diffs still prove zero cold
        compiles under load. Only exists on CompiledProgram — under
        ``MXNET_XLA_STATS=0`` :func:`tracked_jit` returns a plain
        ``jax.jit`` with no warmup surface (see its docstring)."""
        key = self._key(args)
        if key not in self._cache:
            self._compile_entry(key, args)
        return self

    def _key(self, args):
        return tuple(("s", a) if i in self._static and _hashable(a)
                     else _key_of(a) for i, a in enumerate(args))

    def __call__(self, *args, **kwargs):
        import jax
        if threadsan.ARMED:   # one attribute read when off
            threadsan.note_dispatch("compiled.%s" % self.site)
        if kwargs or _any_tracer(args):
            # called inside an outer trace (vjp/scan over a compiled
            # program: some argument leaf is then a tracer) or with
            # kwargs: the plain dispatch path handles both
            with self._mesh_scope():
                return self._fn(*args, **kwargs)
        key = self._key(args)
        entry = self._cache.get(key)
        if entry is None:
            entry = self._compile_entry(key, args)
        else:
            _count("jit_cache_hits_total", self.site,
                   help="tracked jit calls served by a cached executable")
        self.last_flops = entry.flops
        self.last_memory = entry.memory
        if entry.compiled is None:
            try:
                _memprof_dispatch(self.site)
                with self._mesh_scope():
                    return self._fn(*args)
            except Exception as exc:
                oom = _memprof_oom(exc, self.site)
                if oom is not None:
                    raise oom from exc
                raise
        call_args = [a for i, a in enumerate(args) if i not in self._static]
        try:
            _memprof_dispatch(self.site)
            return entry.compiled(*call_args)
        except (TypeError, ValueError) as exc:
            # argument validation the signature key did not capture
            # (e.g. an uncommitted array moved device): disable AOT for
            # this signature and let jit's own cache take over
            logger.warning("compiled[%s]: compiled call rejected (%s); "
                           "falling back to jit dispatch", self.site, exc)
            _count("jit_aot_fallbacks_total", self.site,
                   help="tracked executables rejected at call time")
            entry.compiled = None
            with self._mesh_scope():
                return self._fn(*args)
        except Exception as exc:
            # OOM forensics: a RESOURCE_EXHAUSTED at dispatch re-raises
            # enriched with the memprof verdict (postmortem on disk)
            oom = _memprof_oom(exc, self.site)
            if oom is not None:
                raise oom from exc
            raise

    def _compile_entry(self, key, args):
        with self._compile_lock:
            entry = self._cache.get(key)
            if entry is not None:   # raced with another thread
                _count("jit_cache_hits_total", self.site)
                return entry
            sig = _describe_args(args, self._static)
            with _lock:
                st = _sites.setdefault(self._lineage,
                                       {"compiles": 0, "sig": None})
                st["compiles"] += 1
                n = st["compiles"]
                prev = st["sig"]
                st["sig"] = sig
            reason = None
            if prev is not None:
                reason = explain_signature_change(prev, sig)
                with _lock:
                    _state["last_retrace"] = {
                        "site": self.site, "reason": reason,
                        "compiles": n, "time": time.time()}
                _count("jit_retraces_total", self.site,
                       help="compiles beyond the first at a jit site")
                logger.warning("jit retrace [%s] (compile #%d): %s",
                               self.site, n, reason)
            _count("jit_compiles_total", self.site,
                   help="XLA compiles at tracked jit sites")
            t0 = time.perf_counter()
            compiled = None
            if _aot_enabled():
                try:
                    with self._mesh_scope():
                        compiled = self._fn.lower(*args).compile()
                except Exception as exc:
                    # a RESOURCE_EXHAUSTED at compile would just OOM
                    # again (more confusingly) on the deferred-jit
                    # path: surface it NOW with the memprof verdict
                    oom = _memprof_oom(exc, self.site)
                    if oom is not None:
                        raise oom from exc
                    # other trace/compile errors must surface through
                    # the plain call below, with jit's own diagnostics
                    logger.debug("compiled[%s]: AOT compile failed "
                                 "(%s); deferring to jit dispatch",
                                 self.site, exc)
            dur = time.perf_counter() - t0
            try:
                # run anatomy: compile wall is badput the run-state
                # ledger accounts against training goodput
                from . import runprof
                runprof.note_state("compile", dur, site=self.site)
            except Exception as exc:
                telemetry.swallowed("compiled.runprof", exc)
            flops = _flops_of(compiled) if compiled is not None else None
            memory = _memory_of(compiled) if compiled is not None else None
            telemetry.histogram("jit_compile_seconds",
                                help="lower+compile wall time per tracked "
                                     "jit site", site=self.site).observe(dur)
            telemetry.event("xla.compile", site=self.site, seconds=dur,
                            compile_no=n, flops=flops,
                            retrace=reason)
            meta = {"site": self.site, "seconds": dur, "compile_no": n,
                    "flops": flops, "memory": memory, "time": time.time(),
                    "retrace": reason}
            from . import xla_stats
            xla_stats.flight_recorder.last["compile"] = meta
            if compiled is not None:
                # program anatomy: inventory the executable's collectives
                # and index its ops by scope (one HLO text parse — no
                # compile of its own)
                try:
                    from . import shardprof
                    shardprof.note_program(self.site, self._lineage,
                                           compiled)
                except Exception as exc:
                    telemetry.swallowed("compiled.shardprof", exc)
            if memory is not None:
                xla_stats.ledger_set(self.site, "xla_temp",
                                     memory["temp_bytes"])
                xla_stats.ledger_set(self.site, "xla_output",
                                     memory["output_bytes"])
            entry = _Entry(compiled, flops, memory)
            self._cache[key] = entry
            return entry


def tracked_jit(fun, site, static_argnums=(), lineage=None, policy=None,
                **jit_kwargs):
    """The CompiledProgram factory every jit entry point goes through:
    a :class:`CompiledProgram` under ``site`` (retrace detection scoped
    by ``lineage``), or a plain ``jax.jit`` when compile tracking is
    disabled (``MXNET_XLA_STATS=0``) — the kill switch trades the WHOLE
    CompiledProgram surface (``warmup``/``policy``/``donate_argnums``
    attributes, mesh-scoped dispatch) for jit's own lazy cache, so
    callers needing those must gate on it (training itself still works:
    committed input shardings drive GSPMD without the mesh scope)."""
    if not _enabled():
        import jax
        # mxanalyze: allow(retrace-hazard): pass-through wrapper — static_argnums is forwarded verbatim
        return jax.jit(fun, static_argnums=static_argnums, **jit_kwargs)
    # mxanalyze: allow(retrace-hazard): pass-through wrapper — static_argnums is forwarded verbatim
    return CompiledProgram(fun, site, static_argnums=static_argnums,
                           lineage=lineage, policy=policy, **jit_kwargs)


def aot_compile(jitted, *args):
    """Best-effort AOT compile of an (already jitted) callable for
    ``args``. Returns ``(compiled, info)`` where ``info`` carries
    ``flops``/``memory``; ``(None, None)`` when lowering fails (caller
    keeps using the jitted function)."""
    try:
        compiled = jitted.lower(*args).compile()
    except Exception as exc:
        logger.debug("aot_compile failed: %s", exc)
        return None, None
    return compiled, {"flops": _flops_of(compiled),
                      "memory": _memory_of(compiled)}
