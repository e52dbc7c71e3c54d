"""Program anatomy: collective profiling, the op index, sharding audit,
and overlap verdicts for SPMD programs.

PR 8 moved gradient sync INSIDE the compiled step (sharding constraints
on a named mesh), so the kvstore push/pull counters that used to show
communication now legitimately read zero — the all-reduces, all-gathers
and reduce-scatters that dominate multi-device step time run where no
host-side hook can see them. This module is the layer that makes them
visible again, hooked into the one `CompiledProgram` choke point
(`mxnet_tpu/compiled.py` calls :func:`note_program` next to its
``cost_analysis``/``memory_analysis`` hooks, once per compile):

1. **Collective extractor** — every compiled executable's HLO text
   (``compiled.as_text()``; a ``cost_analysis`` fallback keeps the
   ledger entry when a backend cannot print HLO) is parsed into a
   per-program collective inventory: op kind (all-reduce / all-gather /
   reduce-scatter / collective-permute / all-to-all, async ``-start``
   forms included, ``-done`` halves skipped), instruction count, bytes
   moved (output-shape payload), and replica-group shape. Exported as
   ``spmd_collectives_total{kind=}`` / ``spmd_collective_bytes_total
   {kind=}`` counters plus a per-signature ledger keyed ``(site,
   lineage)`` exactly like the retrace explainer's, so "what does one
   fused-step dispatch put on the wire" is a lookup, not a guess.
   Parsing text of an already-compiled executable triggers NO compile:
   ``xla_stats.compile_counts()`` diffs prove the instrumentation is
   free of retraces (asserted in ``tests/test_shardprof.py``).

2. **Op index** — in the same pass over the same text, every
   instruction that runs as an op of its own (all but those inside a
   fusion) is recorded with the ``op_name`` it was traced under, the
   scope path components (`jax.named_scope`) of what it holds (a
   fusion's: of every instruction of the computation it calls), and
   the ``op_name`` of the convolution it is or holds, traced under the
   scope ``convolution``. A device trace names its ``XLA Ops`` events by
   these instructions and leaves ``op_name`` out, so the index is how
   an op event finds its layer. Kept per ``(site, lineage)``, latest
   compile wins, and looked up by the HLO module's name
   (:func:`op_index`, ``jit_step``, ``jit_scan_step``).

3. **Sharding audit** — :func:`audit` walks a bound Module's (or gluon
   Trainer's) params, grads, and optimizer state and reports spec-vs-
   actual sharding per parameter: ``replicated`` where the policy said
   sharded (the `init_params` bias-bug class PR 8 fixed in
   ``NDArray.__setitem__``), ``mismatch`` for a different layout,
   ``ok`` otherwise. Gauged as ``spmd_replicated_param_bytes`` /
   ``spmd_sharded_param_bytes`` (global bytes by ACTUAL placement) and
   rendered as a table by the report CLI.

4. **Overlap verdict** — measured per-step wall/device time (stepprof)
   + the collective byte inventory + a per-link bandwidth table
   (``MXNET_SHARDPROF_LINK_GBPS`` override, defaults per device kind)
   combine into predicted comm seconds per step and an
   ``spmd_overlap_fraction`` gauge: the share of predicted wire time
   hidden under compute, under the documented estimator
   ``overlap = clamp01((compute_est + C - W) / C)`` with
   ``compute_est = max(D - C, 0)`` (W = mean step wall, D = sampled
   device busy, C = predicted comm). `stepprof.classify` gains a
   ``comm-bound`` class fed by :func:`comm_stats`, with hints keyed to
   ROADMAP items 1-2 (fsdp gather not overlapped -> donation/scan;
   all-reduce ~= grad bytes -> compression / larger per-device batch).

5. **Cross-host** — per-host ``shardprof_host<h>_pid<p>.json``
   snapshots ride the stepprof/reqtrace telemetry-dir transport
   (throttled exporter thread + atexit); the report CLI merges them so
   a MULTICHIP run shows per-host comm bytes and the skew between them.

CLI: ``python -m mxnet_tpu.shardprof report [path|dir]``. Enablement:
``MXNET_SHARDPROF=0`` disables the compile hook (the query API then
reports empty, :func:`op_index` None); recording costs one pass of
regexes over the HLO text per compile, its seconds kept as the
ledger entry's ``parse_s``.

Import cost: stdlib + telemetry + stepprof only — jax is imported
lazily inside the audit helpers, so the report CLI runs on a machine
with no jax at all.

Lock order: this module has ONE lock (``_lock``) guarding the program
ledger and module state; it may call into telemetry (registry lock is
innermost of all) while holding it, never the reverse.
"""
from __future__ import annotations

import atexit
import json
import os
import re
import threading
import time

from . import telemetry
from . import stepprof

__all__ = ["COLLECTIVE_KINDS", "enabled", "parse_hlo_collectives",
           "inventory_of", "read_hlo", "op_index_of", "note_program",
           "programs", "op_index", "site_inventory",
           "train_step_inventory", "collective_totals", "link_gbps",
           "LINK_GBPS_BY_KIND", "comm_stats", "audit", "snapshot",
           "reset", "write_host_snapshot", "merge_host_snapshots",
           "comm_skew", "report", "main"]

#: the collective op kinds the extractor inventories (HLO mnemonics)
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")

#: train-step sites, most specific first: the per-step wire figure of
#: :func:`comm_stats` prefers these over e.g. an inference forward
_TRAIN_SITES = ("module.scan_step", "module.fused_step",
                "data_parallel.step", "executor.forward_backward")

_lock = threading.Lock()
_programs = {}   # (site, lineage) -> inventory entry (latest compile)
_op_indexes = {}  # (site, lineage) -> (module, {instruction: record})
_state = {"param_bytes_global": None, "last_audit": None,
          "export_thread": None}


def enabled():
    """Whether the compile hook records collective inventories
    (``MXNET_SHARDPROF``, default on)."""
    return os.environ.get("MXNET_SHARDPROF", "1") != "0"


def reset():
    """Drop the program ledger and audit state (tests). Registry
    counters are NOT touched — pair with ``telemetry.reset()``."""
    with _lock:
        _programs.clear()
        _op_indexes.clear()
        _state["param_bytes_global"] = None
        _state["last_audit"] = None


# ---------------------------------------------------------------------------
# HLO-text collective extractor
# ---------------------------------------------------------------------------

#: element width in BITS per HLO dtype mnemonic (default 32 for unknown)
_DTYPE_BITS = {
    "pred": 8, "s4": 4, "u4": 4, "s8": 8, "u8": 8, "s16": 16, "u16": 16,
    "s32": 32, "u32": 32, "s64": 64, "u64": 64, "f16": 16, "bf16": 16,
    "f32": 32, "f64": 64, "c64": 64, "c128": 128,
}

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_KIND_RE = re.compile(r"\b(%s)(-start|-done)?\("
                      % "|".join(COLLECTIVE_KINDS))
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=(\{\{[\d,{}\s]*\}\})")


def _shape_bits(dtype, dims):
    elems = 1
    if dims:
        for d in dims.split(","):
            elems *= int(d)
    return elems * _DTYPE_BITS.get(dtype, 32)


def _replica_groups(line):
    """(n_groups, group_size) from the instruction's replica_groups
    attribute, or None when absent/empty. Handles both the iota form
    (``[1,8]<=[8]``) and the explicit list (``{{0,1},{2,3}}``)."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return (int(m.group(1)), int(m.group(2)))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        groups = re.findall(r"\{([\d,\s]*)\}", m.group(1)[1:-1])
        groups = [g for g in groups if g.strip()]
        if groups:
            return (len(groups), len(groups[0].split(",")))
    return None


def _collective(rest, line):
    """The collective of one instruction (``rest`` is its text after
    ``=``), or None: see :func:`parse_hlo_collectives`."""
    km = _KIND_RE.search(rest)
    if km is None or km.group(2) == "-done":
        return None
    is_start = km.group(2) == "-start"
    shapes = _SHAPE_RE.findall(rest[:km.start()])
    if not shapes:
        return None
    if is_start and len(shapes) >= 2:
        # (operand..., output...) tuple: the output half is the wire
        shapes = shapes[len(shapes) // 2:]
    bits = sum(_shape_bits(dt, dims) for dt, dims in shapes)
    return {"kind": km.group(1), "bytes": (bits + 7) // 8,
            "async": is_start, "replica_groups": _replica_groups(line)}


_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OPCODE_RE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_WRAPPED_RE = re.compile(r"^[\w\-]+\((.*)\)$")
#: the opcodes of a convolution's products: XLA may lower a 1x1
#: convolution, or one of its gradients, to a dot
_CONV_OPCODES = ("convolution", "dot")


def _scopes_of(op_name, cache):
    """The scope path components of an ``op_name``: every component but
    the last (the primitive), each unwrapped of its transformations
    (``transpose(jvp(batch_norm))`` is ``batch_norm``, ``jit(step)``
    is ``step``)."""
    got = cache.get(op_name)
    if got is None:
        got = []
        for part in op_name.split("/")[:-1]:
            m = _WRAPPED_RE.match(part)
            while m is not None:
                part = m.group(1)
                m = _WRAPPED_RE.match(part)
            if part:
                got.append(part)
        got = cache[op_name] = frozenset(got)
    return got


def read_hlo(text):
    """One pass over an HLO text: ``(module name, collectives, op
    index)``, the collectives as :func:`parse_hlo_collectives` gives
    them and the op index as :func:`op_index_of` does."""
    module, collectives = None, []
    comps = {}     # computation -> [(instruction, op_name, scopes, conv,
    #                                 callee of a fusion)]
    fused = set()
    cache = {}
    body = None
    for line in text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            m = _COMPUTATION_RE.match(line)
            if m is not None:
                body = comps.setdefault(m.group(1), [])
            elif module is None:
                m = _MODULE_RE.match(line)
                module = m.group(1) if m else None
            continue
        name, rest = m.groups()
        c = _collective(rest, line)
        if c is not None:
            collectives.append(c)
        if body is None:
            continue
        m = _OP_NAME_RE.search(rest)
        op_name = m.group(1) if m else ""
        scopes = _scopes_of(op_name, cache)
        m = _OPCODE_RE.search(rest)
        opcode = m.group(1) if m else ""
        conv = None
        if opcode in _CONV_OPCODES and "convolution" in scopes:
            conv = op_name
        elif opcode == "convolution" and not op_name:
            conv = ""   # rebuilt by a compiler pass, metadata dropped
        callee = None
        if opcode == "fusion":
            m = _CALLS_RE.search(rest)
            if m is not None:
                callee = m.group(1)
                fused.add(callee)
        body.append((name, op_name, scopes, conv, callee))

    held = {}   # computation -> (scopes, conv) of all it holds

    def holds(comp):
        if comp not in held:
            scopes, conv = set(), None
            for _, _, s, c, callee in comps.get(comp, ()):
                if callee is not None:
                    s2, c2 = holds(callee)
                    scopes |= s2
                    conv = c2 if conv is None else conv
                scopes |= s
                conv = c if conv is None else conv
            held[comp] = (frozenset(scopes), conv)
        return held[comp]

    ops = {}
    for comp, instructions in comps.items():
        if comp in fused:
            continue
        for name, op_name, scopes, conv, callee in instructions:
            if callee is not None:
                s2, c2 = holds(callee)
                scopes = scopes | s2
                conv = c2 if conv is None else conv
            ops[name] = {"op_name": op_name, "scopes": scopes,
                         "conv": conv}
    return module, collectives, ops


def parse_hlo_collectives(text):
    """Collective instructions out of an HLO text dump:
    ``[{"kind", "bytes", "async", "replica_groups"}, ...]``.

    Bytes are the payload of the instruction's RESULT shapes — for the
    async ``-start`` form (whose result tuples the operands ahead of the
    outputs) only the output half counts; ``-done`` halves are skipped
    entirely so an async pair is one collective, not two. Mentions of a
    kind inside metadata (``op_name="...all_reduce..."``) never match:
    the pattern anchors on the ``= <shape> <kind>(`` instruction form.
    """
    return read_hlo(text)[1]


def op_index_of(text):
    """The op index of an HLO text: ``{instruction: {"op_name",
    "scopes", "conv"}}`` for every instruction that runs as an op of its
    own, that is every one outside a fused computation (the names a
    device trace's ``XLA Ops`` events carry). ``op_name`` is its own,
    ``""`` where it has none; ``scopes`` the frozenset of scope path
    components of its own ``op_name`` and, for a fusion, of every
    instruction of the computation it calls; ``conv`` the ``op_name`` of
    the convolution (or dot) under the scope ``convolution`` that it is
    or that its fusion holds, ``""`` for a convolution with no
    ``op_name`` at all (a compiler pass rebuilt it from one and dropped
    its metadata: the CPU's weight gradients), else None."""
    return read_hlo(text)[2]


def inventory_of(text):
    """Aggregate :func:`parse_hlo_collectives` output per kind:
    ``{kind: {"count", "bytes", "replica_groups"}}`` (``replica_groups``
    is the sorted list of distinct ``(n_groups, group_size)`` shapes)."""
    return _aggregate(parse_hlo_collectives(text))


def _aggregate(collectives):
    inv = {}
    for c in collectives:
        d = inv.setdefault(c["kind"], {"count": 0, "bytes": 0,
                                       "replica_groups": set()})
        d["count"] += 1
        d["bytes"] += c["bytes"]
        if c["replica_groups"] is not None:
            d["replica_groups"].add(c["replica_groups"])
    for d in inv.values():
        d["replica_groups"] = sorted(d["replica_groups"])
    return inv


# ---------------------------------------------------------------------------
# The compile hook + per-signature ledger
# ---------------------------------------------------------------------------

def _cost_fallback(compiled):
    """Best-effort figures when a backend cannot print HLO: the
    ``bytes accessed`` total of ``cost_analysis`` (NOT wire bytes — a
    placeholder so the ledger still names the program)."""
    try:
        cost = compiled.cost_analysis()
    except Exception as exc:
        telemetry.swallowed("shardprof.cost_analysis", exc)
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    try:
        b = cost.get("bytes accessed")
    except AttributeError:
        return None
    return {"bytes_accessed": float(b)} if b is not None else {}


def note_program(site, lineage, compiled):
    """Record the collective inventory of one freshly compiled
    executable under the ``(site, lineage)`` key the retrace explainer
    uses. Called by ``CompiledProgram._compile_entry`` once per compile;
    parses text only — it can never add a compile or retrace of its
    own. Returns the ledger entry (None when disabled/no executable)."""
    if not enabled() or compiled is None:
        return None
    text = ops = None
    try:
        text = compiled.as_text()
    except Exception as exc:
        telemetry.swallowed("shardprof.hlo_text", exc)
    if text is not None:
        t0 = time.perf_counter()
        module, collectives, ops = read_hlo(text)
        inv = _aggregate(collectives)
        entry = {"site": site, "source": "hlo", "collectives": inv,
                 "bytes": sum(d["bytes"] for d in inv.values()),
                 "module": module, "parse_s": time.perf_counter() - t0,
                 "updated": time.time()}
    else:
        entry = {"site": site, "source": "cost_analysis",
                 "collectives": {}, "bytes": 0, "updated": time.time(),
                 "cost": _cost_fallback(compiled)}
    with _lock:
        prev = _programs.get((site, lineage))
        entry["compiles"] = (prev["compiles"] + 1) if prev else 1
        _programs[(site, lineage)] = entry
        if ops is not None:
            _op_indexes[(site, lineage)] = (entry["module"], ops)
        else:
            _op_indexes.pop((site, lineage), None)
    for kind, d in entry["collectives"].items():
        telemetry.counter("spmd_collectives_total",
                          help="collective instructions in compiled "
                               "SPMD programs, by kind").inc(d["count"])
        telemetry.counter("spmd_collectives_total",
                          kind=kind).inc(d["count"])
        telemetry.counter("spmd_collective_bytes_total",
                          help="payload bytes of collectives in compiled "
                               "SPMD programs, by kind").inc(d["bytes"])
        telemetry.counter("spmd_collective_bytes_total",
                          kind=kind).inc(d["bytes"])
    _maybe_export()
    return entry


def programs():
    """Copy of the per-signature ledger:
    ``{(site, lineage): entry}`` — latest compile per key."""
    with _lock:
        return dict(_programs)


def op_index(module):
    """The op index (:func:`op_index_of`) of the latest compile of the
    HLO module named ``module`` (``jit_step``: a trace's ``XLA Modules``
    event name up to its fingerprint), or None: no such compile, or the
    hook is off (``MXNET_SHARDPROF=0``)."""
    with _lock:
        found = [(_programs[key]["updated"], ops)
                 for key, (name, ops) in _op_indexes.items()
                 if name == module]
    return max(found, key=lambda f: f[0])[1] if found else None


def site_inventory(site):
    """Latest inventory entry compiled under ``site`` (two models
    hitting one site keep separate lineages; the freshest wins), or
    None."""
    with _lock:
        entries = [e for (s, _l), e in _programs.items() if s == site]
    if not entries:
        return None
    return max(entries, key=lambda e: e["updated"])


def train_step_inventory():
    """The inventory entry of the live TRAIN-step program: the freshest
    entry among the known train sites (scan/fused step, data_parallel,
    executor fwd_bwd), falling back to the freshest entry overall."""
    for site in _TRAIN_SITES:
        entry = site_inventory(site)
        if entry is not None and entry["collectives"]:
            return entry
    with _lock:
        entries = [e for e in _programs.values() if e["collectives"]]
    if not entries:
        return None
    return max(entries, key=lambda e: e["updated"])


def collective_totals():
    """{kind: {"count", "bytes"}} summed over the latest program of
    every (site, lineage) — the process-wide compiled-inventory view."""
    out = {}
    for entry in programs().values():
        for kind, d in entry["collectives"].items():
            t = out.setdefault(kind, {"count": 0, "bytes": 0})
            t["count"] += d["count"]
            t["bytes"] += d["bytes"]
    return out


# ---------------------------------------------------------------------------
# Link bandwidth + overlap verdict
# ---------------------------------------------------------------------------

#: per-chip interconnect bandwidth in GB/s by device-kind substring
#: (ICI for TPU, NVLink for GPU) — order-of-magnitude roofline figures
#: for the comm-time estimate, not datasheet precision. Matched
#: case-insensitively, longest name first; override per link with
#: MXNET_SHARDPROF_LINK_GBPS.
LINK_GBPS_BY_KIND = {
    "tpu v2": 62.0,
    "tpu v3": 82.0,
    "tpu v4": 300.0,
    "tpu v5 lite": 200.0,
    "tpu v5e": 200.0,
    "tpu v5p": 600.0,
    "tpu v6 lite": 448.0,
    "tpu v6e": 448.0,
    "a100": 600.0,
    "h100": 900.0,
    "h200": 900.0,
    "v100": 300.0,
    "cpu": 8.0,   # host-memory "fabric" of the forced CPU test mesh
}


def link_gbps():
    """Per-link bandwidth in GB/s: ``MXNET_SHARDPROF_LINK_GBPS`` env if
    set, else the device-kind table; 0.0 when unknown (comm predictions
    then read None rather than inventing a wire)."""
    env = os.environ.get("MXNET_SHARDPROF_LINK_GBPS")
    if env:
        try:
            return float(env)
        except ValueError:
            import warnings
            warnings.warn("bad MXNET_SHARDPROF_LINK_GBPS=%r ignored"
                          % (env,))
    try:
        import jax
        kind = jax.devices()[0].device_kind.lower()
    except Exception as exc:
        telemetry.swallowed("shardprof.link_gbps", exc)
        return 0.0
    for name in sorted(LINK_GBPS_BY_KIND, key=len, reverse=True):
        if name in kind:
            return LINK_GBPS_BY_KIND[name]
    return 0.0


def _clamp01(x):
    return max(0.0, min(1.0, x))


def comm_stats(site=None, gbps=None):
    """Predicted communication anatomy of the live train step, or None
    when no collective inventory (or no bandwidth figure) exists.

    Combines the per-dispatch collective bytes (``site`` or the train
    sites), the per-link bandwidth table, and stepprof's measured step
    stats into::

        {"site", "bytes_per_step", "by_kind", "dominant_kind",
         "predicted_comm_seconds", "step_seconds", "comm_fraction",
         "overlap_fraction", "param_gather_ratio", "link_gbps"}

    ``comm_fraction`` is predicted wire seconds over mean step wall.
    ``overlap_fraction`` estimates the share of predicted comm hidden
    under compute (``clamp01((compute_est + C - W) / C)``,
    ``compute_est = max(D - C, 0)`` with D the sampled device-busy
    mean): 0 = fully exposed (a serial step, W = compute + C), 1 =
    fully hidden (W = compute). None until a sampled-sync device
    measurement exists. ``param_gather_ratio`` is (all-gather +
    reduce-scatter bytes) over the last audit's global param bytes —
    ~1.0 reads "the fsdp weight gather runs every step". Also sets the
    ``spmd_predicted_comm_seconds`` / ``spmd_comm_fraction`` /
    ``spmd_overlap_fraction`` gauges."""
    entry = site_inventory(site) if site else train_step_inventory()
    if entry is None or not entry["collectives"]:
        return None
    bw = gbps if gbps is not None else link_gbps()
    if bw <= 0:
        return None
    by_kind = {k: d["bytes"] for k, d in entry["collectives"].items()}
    total = sum(by_kind.values())
    if total <= 0:
        return None
    C = total / (bw * 1e9)
    out = {"site": entry["site"], "bytes_per_step": total,
           "by_kind": by_kind,
           "dominant_kind": max(by_kind, key=lambda k: by_kind[k]),
           "predicted_comm_seconds": C, "link_gbps": bw,
           "step_seconds": None, "comm_fraction": None,
           "overlap_fraction": None, "param_gather_ratio": None}
    st = stepprof.profiler.step_stats()
    W = st.get("mean_step_seconds") or 0.0
    if W > 0:
        out["step_seconds"] = W
        out["comm_fraction"] = _clamp01(C / W)
        D = stepprof.profiler.overlap().get("device_busy_est")
        # C >= W means the prediction exceeds the whole measured step —
        # the bandwidth figure is inconsistent with reality and the
        # overlap estimate would read "fully hidden" exactly when comm
        # looks worst, so it stays None rather than misleading
        if D and C < W:
            compute_est = max(D - C, 0.0)
            out["overlap_fraction"] = _clamp01((compute_est + C - W) / C)
    with _lock:
        pb = _state["param_bytes_global"]
    gather = by_kind.get("all-gather", 0) + by_kind.get("reduce-scatter", 0)
    if pb and gather:
        out["param_gather_ratio"] = gather / pb
    telemetry.gauge("spmd_predicted_comm_seconds",
                    help="predicted collective wire seconds per train "
                         "step (bytes / link bandwidth)").set(C)
    if out["comm_fraction"] is not None:
        telemetry.gauge("spmd_comm_fraction",
                        help="predicted comm seconds over mean step "
                             "wall").set(out["comm_fraction"])
    if out["overlap_fraction"] is not None:
        telemetry.gauge("spmd_overlap_fraction",
                        help="estimated share of predicted comm time "
                             "hidden under compute").set(
                                 out["overlap_fraction"])
    return out


# ---------------------------------------------------------------------------
# Sharding audit
# ---------------------------------------------------------------------------

def _leaf_placement(arr):
    """("replicated" | "sharded" | "single" | "unknown", spec_tuple or
    None, nbytes) of one array leaf (NDArrays unwrapped)."""
    from .parallel import spmd as spmd_mod
    a = getattr(arr, "_data", arr)
    nbytes = int(getattr(a, "nbytes", 0) or 0)
    sh = getattr(a, "sharding", None)
    if sh is None:
        return "unknown", None, nbytes
    spec = getattr(sh, "spec", None)
    if spec is not None:
        tup = spmd_mod.spec_tuple(spec)
        return ("sharded" if tup else "replicated"), tup, nbytes
    try:
        ndev = len(sh.device_set)
        if ndev <= 1:
            return "single", None, nbytes
        return ("replicated" if sh.is_fully_replicated else "sharded",
                None, nbytes)
    except Exception as exc:   # non-XLA sharding object
        telemetry.swallowed("shardprof.placement", exc)
        return "unknown", None, nbytes


def _audit_row(name, shape, arr, policy, kind):
    from .parallel import spmd as spmd_mod
    expected = None
    if policy is not None:
        expected = spmd_mod.spec_tuple(policy.param_spec(name, shape))
    placement, actual, nbytes = _leaf_placement(arr)
    if expected is None:
        status = "ok"
    elif placement in ("single", "unknown"):
        status = "unplaced" if expected else "ok"
    elif actual is not None:
        status = "ok" if actual == expected else (
            "replicated" if not actual and expected else "mismatch")
    else:   # no spec on the sharding object: judge replication only
        status = "ok" if bool(expected) == (placement == "sharded") \
            else ("replicated" if expected else "mismatch")
    return {"name": name, "kind": kind, "shape": tuple(shape),
            "bytes": nbytes, "expected": expected, "actual": actual,
            "placement": placement, "status": status}


def _module_entries(mod):
    """(policy, [(name, shape, array, kind)]) off a bound Module."""
    policy = getattr(mod, "_spmd", None)
    exec_ = mod._exec
    out = []
    for name in mod._param_names:
        arr = exec_.arg_dict.get(name)
        if arr is None:
            continue
        out.append((name, arr.shape, arr, "param"))
        g = exec_.grad_dict.get(name)
        if g is not None:
            out.append((name, g.shape, g, "grad"))
    updater = getattr(mod, "_updater", None)
    if updater is not None:
        for idx, state in getattr(updater, "states", {}).items():
            try:
                pname = mod._param_names[idx]
            except (IndexError, TypeError):
                pname = str(idx)
            for j, leaf in enumerate(_state_leaves(state)):
                out.append(("%s/state%d" % (pname, j), leaf.shape, leaf,
                            "opt_state"))
    return policy, out


def _state_leaves(state):
    if state is None:
        return []
    if isinstance(state, (list, tuple)):
        leaves = []
        for s in state:
            leaves.extend(_state_leaves(s))
        return leaves
    return [state] if hasattr(state, "shape") else []


def _trainer_entries(trainer):
    policy = getattr(trainer, "_spmd", None)
    out = []
    for param in trainer._params:
        try:
            data = param.data()
        except Exception as exc:   # deferred-init param: nothing bound
            telemetry.swallowed("shardprof.trainer_param", exc)
            continue
        out.append((param.name, data.shape, data, "param"))
        grad = getattr(param, "_grad", None)
        if isinstance(grad, (list, tuple)):
            grad = grad[0] if grad else None
        if grad is not None and hasattr(grad, "shape"):
            out.append((param.name, grad.shape, grad, "grad"))
    return policy, out


def audit(obj, policy=None):
    """Spec-vs-actual sharding audit of a bound ``Module``, a gluon
    ``Trainer``, or a plain ``{name: array}`` dict (then pass
    ``policy=``). Walks params, gradients, and optimizer state; each
    row gets a status:

    - ``ok`` — placement matches the policy's spec (or no policy to
      audit against);
    - ``replicated`` — the policy said sharded but the buffer is fully
      replicated (the silent-bias-replication class of bug);
    - ``mismatch`` — sharded, but on a different layout than the spec;
    - ``unplaced`` — single-device/unknown placement where the policy
      expected a mesh.

    Returns ``{"policy", "rows", "flagged", "replicated_bytes",
    "sharded_bytes", "param_bytes_global"}`` and sets the
    ``spmd_replicated_param_bytes`` / ``spmd_sharded_param_bytes``
    gauges (PARAM rows only, global bytes by actual placement). The
    global param bytes also feed :func:`comm_stats`'
    ``param_gather_ratio``."""
    if hasattr(obj, "_param_names") and hasattr(obj, "_exec"):
        pol, entries = _module_entries(obj)
    elif hasattr(obj, "_params") and hasattr(obj, "_spmd"):
        pol, entries = _trainer_entries(obj)
    elif isinstance(obj, dict):
        pol = None
        entries = [(n, a.shape, a, "param") for n, a in obj.items()]
    else:
        raise TypeError("audit() wants a bound Module, a gluon Trainer, "
                        "or a {name: array} dict; got %r" % (obj,))
    pol = policy if policy is not None else pol
    rows = [_audit_row(n, s, a, pol, k) for n, s, a, k in entries]
    repl = shard = params_global = 0
    for r in rows:
        if r["kind"] != "param":
            continue
        params_global += r["bytes"]
        if r["placement"] == "sharded":
            shard += r["bytes"]
        else:
            repl += r["bytes"]
    flagged = [r["name"] for r in rows if r["status"] != "ok"]
    telemetry.gauge("spmd_replicated_param_bytes",
                    help="global bytes of params whose buffers are "
                         "fully replicated (or unplaced)").set(repl)
    telemetry.gauge("spmd_sharded_param_bytes",
                    help="global bytes of params whose buffers are "
                         "mesh-sharded").set(shard)
    out = {"policy": pol.name if pol is not None else None,
           "rows": rows, "flagged": flagged,
           "replicated_bytes": repl, "sharded_bytes": shard,
           "param_bytes_global": params_global}
    with _lock:
        _state["param_bytes_global"] = params_global or None
        _state["last_audit"] = {
            "policy": out["policy"], "flagged": flagged,
            "replicated_bytes": repl, "sharded_bytes": shard,
            "rows": len(rows),
            "bad_rows": [r for r in rows if r["status"] != "ok"][:40]}
    return out


# ---------------------------------------------------------------------------
# Snapshots + cross-host merge (stepprof/reqtrace transport)
# ---------------------------------------------------------------------------

def snapshot():
    """One JSON-able view: identity, per-site inventories, totals, comm
    verdict, last audit summary."""
    per_site = {}
    for (site, _lineage), entry in programs().items():
        cur = per_site.get(site)
        if cur is None or entry["updated"] > cur["updated"]:
            per_site[site] = entry
    comm = comm_stats()
    with _lock:
        last_audit = _state["last_audit"]
    return {"host": telemetry.host_id(), "pid": os.getpid(),
            "updated": time.time(), "sites": per_site,
            "totals": collective_totals(), "comm": comm,
            "audit": last_audit,
            "steps": stepprof.profiler.step_stats()["steps"]}


def write_host_snapshot(dir=None, force=False):
    """Write this process's ``shardprof_host<h>_pid<p>.json`` into
    ``dir`` (default: the configured telemetry dir; None and no dir ->
    no-op) via `telemetry.write_host_json` — the one per-host snapshot
    transport stepprof and reqtrace ride too."""
    if not force and not programs():
        return None
    return telemetry.write_host_json("shardprof", snapshot(), dir=dir)


def _export_interval():
    try:
        return float(os.environ.get("MXNET_SHARDPROF_SNAPSHOT_EVERY",
                                    "5"))
    except ValueError:
        import warnings
        warnings.warn("bad MXNET_SHARDPROF_SNAPSHOT_EVERY=%r ignored"
                      % (os.environ["MXNET_SHARDPROF_SNAPSHOT_EVERY"],))
        return 5.0


def _maybe_export():
    """Start the background snapshot exporter on the first recorded
    program while a telemetry dir is configured — the exporter thread,
    not the compile path, pays the (possibly NFS) file I/O."""
    if telemetry.configured_dir() is None:
        return
    interval = _export_interval()
    if interval <= 0:
        return
    with _lock:
        if _state["export_thread"] is not None:
            return
        t = threading.Thread(target=_export_loop, args=(interval,),
                             daemon=True,
                             name="mxnet_tpu-shardprof-export")
        _state["export_thread"] = t
    t.start()


def _export_loop(interval):
    while True:
        time.sleep(interval)
        if telemetry.configured_dir() is None:
            continue   # dir unconfigured mid-run: idle, not dead
        try:
            write_host_snapshot()
        except Exception as exc:
            telemetry.swallowed("shardprof.export", exc)


def _atexit_snapshot():
    try:
        write_host_snapshot()
    except Exception as exc:
        telemetry.swallowed("shardprof.atexit", exc)


atexit.register(_atexit_snapshot)


def merge_host_snapshots(dir=None):
    """Read every ``shardprof_host*.json`` under ``dir`` (default: the
    configured telemetry dir), keeping the freshest snapshot per host
    (`telemetry.merge_host_json`). Returns {host_id: snapshot_dict}."""
    return telemetry.merge_host_json("shardprof", dir)


def comm_skew(dir=None):
    """Cross-host comm skew over merged snapshots: per-host collective
    bytes and predicted comm seconds, skew = max - min predicted comm
    seconds (0 until two hosts report). Publishes the
    ``spmd_comm_skew_seconds`` gauge. Returns ``{"skew_seconds",
    "slow_host", "hosts": {host: {"bytes", "comm_seconds"}}}``."""
    merged = merge_host_snapshots(dir)
    hosts = {}
    for h, doc in merged.items():
        comm = doc.get("comm") or {}
        tot = sum(int(d.get("bytes", 0))
                  for d in (doc.get("totals") or {}).values())
        hosts[h] = {"bytes": tot,
                    "comm_seconds": comm.get("predicted_comm_seconds")}
    sk = comm_skew_from(merged)   # the ONE skew/slow-host computation
    telemetry.gauge("spmd_comm_skew_seconds",
                    help="max-min predicted per-step comm seconds "
                         "across hosts (0 until two report)").set(
                             sk["skew_seconds"])
    return {"skew_seconds": sk["skew_seconds"],
            "slow_host": sk["slow_host"], "hosts": hosts}


# ---------------------------------------------------------------------------
# Report CLI: python -m mxnet_tpu.shardprof report [path|dir]
# ---------------------------------------------------------------------------

def _load_report_source(path):
    """Resolve a report source into ``{"snapshots": {host: doc},
    "source"}``: a snapshot file, a host-snapshot dir, or (path=None)
    the telemetry dir, falling back to the live process."""
    if path is None:
        d = telemetry.configured_dir() \
            or os.environ.get("MXNET_TELEMETRY_DIR")
        merged = merge_host_snapshots(d) if d else {}
        if merged:
            return {"snapshots": merged, "source": d}
        if programs():
            return {"snapshots": {telemetry.host_id(): snapshot()},
                    "source": "live process"}
        return {"snapshots": {}, "source": "none"}
    if os.path.isdir(path):
        return {"snapshots": merge_host_snapshots(path), "source": path}
    with open(path, "r", encoding="utf-8") as fh:
        doc = json.load(fh)
    return {"snapshots": {int(doc.get("host", 0)): doc}, "source": path}


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return "%.1f %s" % (n, unit) if unit != "B" \
                else "%d B" % int(n)
        n /= 1024.0
    return "%d B" % int(n)


def report(path=None, out=None, json_only=False):
    """Render the communication-anatomy report; returns the process
    exit code (0 = data was found, 1 = none)."""
    import sys
    out = out or sys.stdout
    src = _load_report_source(path)
    hosts = src["snapshots"]
    if not json_only:
        out.write("Communication anatomy (%s)\n" % src["source"])
    totals = {}
    for doc in hosts.values():
        for kind, d in (doc.get("totals") or {}).items():
            t = totals.setdefault(kind, {"count": 0, "bytes": 0})
            t["count"] += int(d.get("count", 0))
            t["bytes"] += int(d.get("bytes", 0))
    comm = None
    for doc in hosts.values():
        c = doc.get("comm")
        if c and (comm is None
                  or (c.get("comm_fraction") or 0)
                  > (comm.get("comm_fraction") or 0)):
            comm = c
    audit_doc = None
    for doc in hosts.values():
        a = doc.get("audit")
        if a and (audit_doc is None or a.get("flagged")):
            audit_doc = a
    sk = comm_skew_from(hosts) if len(hosts) >= 2 else None
    if not json_only:
        if totals:
            width = max(len(k) for k in totals)
            for kind in sorted(totals, key=lambda k: -totals[k]["bytes"]):
                d = totals[kind]
                out.write("  %-*s x%-3d %12s\n"
                          % (width, kind, d["count"],
                             _fmt_bytes(d["bytes"])))
        else:
            out.write("  (no collectives recorded)\n")
        if comm:
            out.write("  comm: %s/step over %s at %.0f GB/s -> %.3fms "
                      "predicted\n"
                      % (_fmt_bytes(comm["bytes_per_step"]),
                         comm.get("site"), comm.get("link_gbps", 0.0),
                         comm["predicted_comm_seconds"] * 1e3))
            if comm.get("comm_fraction") is not None:
                line = "  comm share: %.0f%% of step wall" \
                    % (comm["comm_fraction"] * 100.0)
                if comm.get("overlap_fraction") is not None:
                    line += ", overlap %.0f%% hidden under compute" \
                        % (comm["overlap_fraction"] * 100.0)
                out.write(line + "\n")
        if audit_doc:
            out.write("  audit[%s]: %d rows, %d flagged"
                      % (audit_doc.get("policy"),
                         audit_doc.get("rows", 0),
                         len(audit_doc.get("flagged") or [])))
            if audit_doc.get("flagged"):
                out.write(" (%s)" % ", ".join(audit_doc["flagged"][:6]))
            out.write("\n")
            for r in (audit_doc.get("bad_rows") or [])[:10]:
                out.write("    %-28s %-9s expected %s, actual %s\n"
                          % (r.get("name"), r.get("status"),
                             r.get("expected"),
                             r.get("actual")
                             if r.get("actual") is not None
                             else r.get("placement")))
        if sk is not None:
            out.write("  hosts: %d, comm skew %.4fs"
                      % (len(hosts), sk["skew_seconds"]))
            if sk["slow_host"] != -1:
                out.write(", slow host %d" % sk["slow_host"])
            out.write("\n")
    # the verdict judges the SNAPSHOT's comm data: live step shares only
    # belong when the source IS this process (classifying another run's
    # snapshot against this process's shares would mislead), and a comm
    # figure that does not dominate reads "not comm-bound" rather than
    # stepprof's share-verdict for shares this report never loaded
    sh = stepprof.shares() if src["source"] == "live process" else {}
    v, hint = stepprof.classify(sh, comm=comm)
    if comm and v != "comm-bound":
        v = "not-comm-bound"
        cf = comm.get("comm_fraction")
        hint = ("predicted comm is %s of the step wall — the wire is "
                "not the bottleneck; see stepprof report for the "
                "host/device anatomy"
                % ("%.0f%%" % (cf * 100.0) if cf is not None
                   else "an unknown share"))
    if not json_only and comm:
        out.write("  verdict: %s\n  hint: %s\n" % (v, hint))
    rec = {"metric": "shardprof_report", "source": src["source"],
           "collectives": totals, "verdict": v if comm else None}
    if comm:
        rec["bytes_per_step"] = comm["bytes_per_step"]
        rec["comm_fraction"] = comm.get("comm_fraction")
        rec["overlap_fraction"] = comm.get("overlap_fraction")
    if audit_doc:
        rec["audit_flagged"] = len(audit_doc.get("flagged") or [])
    if sk is not None:
        rec["comm_skew_seconds"] = sk["skew_seconds"]
    out.write(json.dumps(rec) + "\n")
    return 0 if totals else 1


def comm_skew_from(hosts):
    """Skew over already-merged snapshot docs (no disk access) — the
    report helper behind :func:`comm_skew`'s directory form."""
    secs = {}
    for h, doc in hosts.items():
        c = doc.get("comm") or {}
        if c.get("predicted_comm_seconds") is not None:
            secs[int(h)] = float(c["predicted_comm_seconds"])
    if len(secs) < 2:
        return {"skew_seconds": 0.0, "slow_host": -1}
    slow = max(secs, key=lambda h: secs[h])
    return {"skew_seconds": secs[slow] - min(secs.values()),
            "slow_host": slow}


def main(argv=None):
    import argparse
    import sys
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.shardprof",
        description="Communication anatomy report: collective "
                    "inventory, sharding audit, overlap verdict, "
                    "cross-host comm skew")
    ap.add_argument("command", choices=["report"],
                    help="'report': render the comm anatomy of a run")
    ap.add_argument("path", nargs="?", default=None,
                    help="a shardprof snapshot JSON, a telemetry dir of "
                         "host snapshots, or nothing (default: "
                         "MXNET_TELEMETRY_DIR, then the live process)")
    ap.add_argument("--json", action="store_true",
                    help="machine line only, no table")
    args = ap.parse_args(argv)
    return report(args.path, json_only=args.json)


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1:]))
