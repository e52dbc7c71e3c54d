"""Profiler.

Parity with reference `python/mxnet/profiler.py` (set_config/set_state/
dump/dumps/pause/resume) and `src/profiler/`:

- Tracing delegates to `jax.profiler` — traces are XPlane/perfetto,
  viewable in TensorBoard or perfetto.dev (superset of the reference's
  chrome://tracing output). `MXNET_PROFILER_AUTOSTART=1` honored
  (reference docs/faq/env_var.md:105).
- ``set_config(aggregate_stats=True)`` enables the in-process aggregate
  table (reference `src/profiler/aggregate_stats.cc`): every eager op
  dispatch and every compiled executor call is timed and folded into a
  per-name count/total/min/max/avg table; ``dumps()`` returns it.
- ``profile_memory=True`` additionally tracks bytes allocated per op
  (output buffers) and samples the backend allocator's
  ``bytes_in_use``/``peak_bytes_in_use`` (reference
  `src/profiler/storage_profiler.h` GpuDeviceStorageProfiler).

Timing caveat: aggregate mode synchronizes after each measured call so the
numbers are wall-clock per dispatch, host overhead included — profile
on-device loops with the tracer instead.
"""
from __future__ import annotations

import os
import time

import jax

from . import telemetry

__all__ = ["set_config", "set_state", "dump", "dumps", "device_dumps",
           "pause", "resume", "reset_stats"]

_state = {"running": False, "dir": "profile_output", "configured": False,
          "paused": False}
_agg = {
    "enabled": False,
    "memory": False,
    "ops": {},          # name -> [count, total_us, min_us, max_us]
    "alloc": {},        # name -> [count, total_bytes, min_bytes, max_bytes]
}


def set_config(filename="profile.json", profile_all=False, profile_symbolic=True,
               profile_imperative=True, profile_memory=True, profile_api=True,
               aggregate_stats=False, **kwargs):
    _state["dir"] = os.path.splitext(filename)[0] + "_trace"
    _state["configured"] = True
    # aggregate mode is a separate opt-in (like the reference): it
    # synchronizes every dispatch, which profile_all users capturing a
    # trace must not silently pay
    _agg["enabled"] = bool(aggregate_stats)
    _agg["memory"] = bool(profile_memory and aggregate_stats)


def set_state(state="stop", profile_process="worker"):
    if state == "run":
        if not _state["running"]:
            jax.profiler.start_trace(_state["dir"])
            _state["running"] = True
    elif state == "stop":
        if _state["running"]:
            jax.profiler.stop_trace()
            _state["running"] = False
    else:
        raise ValueError("state must be 'run' or 'stop'")


def dump(finished=True, profile_process="worker"):
    if _state["running"] and finished:
        set_state("stop")


def pause(profile_process="worker"):
    if _state["running"]:
        jax.profiler.stop_trace()
        _state["running"] = False
        _state["paused"] = True


def resume(profile_process="worker"):
    """Resume a paused trace. A bare ``resume()`` with no prior
    ``set_config``/``pause`` used to silently start a trace into the
    default directory — now it warns and does nothing: resume is the
    second half of a pause/resume pair, not a start button."""
    if _state["running"]:
        return
    if not (_state["configured"] or _state["paused"]):
        import warnings
        warnings.warn(
            "profiler.resume() called before set_config()/pause(): no "
            "trace is configured, nothing to resume — call set_config() "
            "and set_state('run') to start one", stacklevel=2)
        return
    jax.profiler.start_trace(_state["dir"])
    _state["running"] = True
    _state["paused"] = False


# ---------------------------------------------------------------------------
# Aggregate statistics (reference src/profiler/aggregate_stats.cc)
# ---------------------------------------------------------------------------

def aggregate_enabled():
    return _agg["enabled"]


def memory_enabled():
    return _agg["memory"]


def record_op(name, dur_s, out_bytes=0):
    """Fold one timed dispatch into the aggregate table. Called by the
    eager dispatcher (`ops/invoke.py`) and the executor's compiled calls.
    Also feeds the run-level telemetry registry, so op dispatch shows up
    next to kvstore/checkpoint/retry series in `telemetry.dumps()`."""
    telemetry.histogram("op_dispatch_seconds",
                        help="timed dispatches (aggregate mode), by op",
                        op=name).observe(dur_s)
    us = dur_s * 1e6
    rec = _agg["ops"].get(name)
    if rec is None:
        _agg["ops"][name] = [1, us, us, us]
    else:
        rec[0] += 1
        rec[1] += us
        rec[2] = min(rec[2], us)
        rec[3] = max(rec[3], us)
    if _agg["memory"] and out_bytes:
        mrec = _agg["alloc"].get(name)
        if mrec is None:
            _agg["alloc"][name] = [1, out_bytes, out_bytes, out_bytes]
        else:
            mrec[0] += 1
            mrec[1] += out_bytes
            mrec[2] = min(mrec[2], out_bytes)
            mrec[3] = max(mrec[3], out_bytes)


def reset_stats():
    _agg["ops"].clear()
    _agg["alloc"].clear()


def _device_memory_lines():
    """Per-device allocator lines from the `xla_stats` memory ledger.
    Backends without ``memory_stats()`` (CPU) report the ledger's
    live-buffer estimate instead of being skipped, so the table shape —
    and the Prometheus ``hbm_bytes_in_use`` series the ledger sets —
    stay continuous on CPU runs."""
    from . import xla_stats
    return ["Device %s: bytes_in_use=%d peak_bytes_in_use=%d"
            % (rec["device"], rec["bytes_in_use"],
               rec["peak_bytes_in_use"])
            for rec in xla_stats.device_memory(limit=8)]


def dumps(reset=False, format="table"):
    """Aggregate-stats table (reference profiler.dumps ->
    AggregateStats::DumpTable). Empty string when aggregate mode is off —
    matching the reference when no stats were collected."""
    if not _agg["ops"] and not _agg["alloc"]:
        return ""
    out = ["Profile Statistics.", "\tNote: aggregate statistics over all "
           "timed dispatches since the last reset."]
    hdr = ("%-32s %12s %14s %14s %14s %14s"
           % ("Name", "Total Count", "Time (ms)", "Min Time (ms)",
              "Max Time (ms)", "Avg Time (ms)"))
    out += ["", hdr, "-" * len(hdr)]
    for name in sorted(_agg["ops"], key=lambda n: -_agg["ops"][n][1]):
        cnt, tot, mn, mx = _agg["ops"][name]
        out.append("%-32s %12d %14.4f %14.4f %14.4f %14.4f"
                   % (name[:32], cnt, tot / 1e3, mn / 1e3, mx / 1e3,
                      tot / cnt / 1e3))
    if _agg["memory"]:
        out += ["", "Memory allocations (op output buffers)."]
        hdr = ("%-32s %12s %14s %14s %14s"
               % ("Name", "Total Count", "Total Bytes", "Min Bytes",
                  "Max Bytes"))
        out += [hdr, "-" * len(hdr)]
        for name in sorted(_agg["alloc"], key=lambda n: -_agg["alloc"][n][1]):
            cnt, tot, mn, mx = _agg["alloc"][name]
            out.append("%-32s %12d %14d %14d %14d"
                       % (name[:32], cnt, tot, mn, mx))
        mem_lines = _device_memory_lines()
        if mem_lines:
            out += ["", "Backend allocator (PJRT memory_stats)."] + mem_lines
    if reset:
        reset_stats()
    return "\n".join(out) + "\n"


def device_dumps(logdir=None, line_filter=None, by="op", top=40):
    """Per-op *device-time* table from the captured XPlane trace — the
    analog of the reference's engine-instrumented aggregate stats
    (`src/profiler/aggregate_stats.cc`), measured on the device timeline
    instead of host wall-clock.  Requires a completed trace
    (``set_state('stop')`` first)."""
    from . import xplane
    return xplane.dumps(logdir or _state["dir"], line_filter=line_filter,
                        by=by, top=top)


class Scope:
    """Annotate a region in the trace (reference profiler scopes)."""

    def __init__(self, name="<unk>"):
        self._ctx = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._ctx.__enter__()
        return self

    def __exit__(self, *a):
        return self._ctx.__exit__(*a)


def finish_timed(name, t0, outs):
    """Synchronize ``outs``, then fold (name, elapsed, output bytes) into
    the aggregate table. Dispatch sites call this only when
    ``aggregate_enabled()``."""
    jax.block_until_ready(outs)
    nbytes = 0
    if _agg["memory"]:
        for leaf in jax.tree.leaves(outs):
            nbytes += getattr(leaf, "nbytes", 0)
    record_op(name, time.perf_counter() - t0, nbytes)


if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1":
    # MXNET_PROFILER_AGGREGATE=1 makes the autostarted run ALSO collect
    # the aggregate table (reference env_var.md: autostart alone only
    # captures the trace); dumps() then has data without code changes
    set_config(aggregate_stats=os.environ.get(
        "MXNET_PROFILER_AGGREGATE", "0") == "1")
    set_state("run")
