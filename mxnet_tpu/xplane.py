"""XPlane trace parser: per-op *device-time* attribution.

The reference profiler's aggregate table measures operator execution time
inside the engine (reference ``src/profiler/aggregate_stats.cc``,
``src/engine/threaded_engine.h:80``).  Our in-process table
(`mxnet_tpu/profiler.py`) times host wall-clock per dispatch, which for
a short op measures the host's dispatch, not the op.  This module closes
that gap: it reads the XPlane protobuf that ``jax.profiler`` captures and
aggregates *device* time per XLA op / HLO category, answering "where do
the backward milliseconds go" from the device's own timeline.

No TensorBoard plugin is required: the XPlane wire format is decoded with
a ~60-line generic protobuf reader (schema:
tensorflow/tsl/profiler/protobuf/xplane.proto, stable since 2020).

Usage::

    import mxnet_tpu as mx
    mx.profiler.set_config(filename='net')        # trace dir net_trace/
    mx.profiler.set_state('run')
    ... run steps ...
    mx.profiler.set_state('stop')
    print(mx.xplane.dumps('net_trace'))           # per-op device table

or from the shell::

    python -m mxnet_tpu.xplane net_trace --top 30

For offline analysis (no JAX install / no package import) the file is
self-contained stdlib Python — run it directly::

    python mxnet_tpu/xplane.py net_trace --top 30
"""
from __future__ import annotations

import json
import os
import re

__all__ = ["parse_xspace", "find_xplane_files", "op_table", "dumps",
           "Plane", "Line", "Event"]


# ---------------------------------------------------------------------------
# Generic protobuf wire decoding
# ---------------------------------------------------------------------------

def _varint(buf, i):
    r = 0
    s = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        if not b & 0x80:
            return r, i
        s += 7


def _signed(v):
    """Interpret a decoded varint as int64 (plain two's-complement)."""
    return v - (1 << 64) if v >= (1 << 63) else v


def _fields(buf):
    """Decode one message into a {field_number: [raw values]} dict.
    Length-delimited payloads stay as bytes for the caller to interpret."""
    i = 0
    out = {}
    n = len(buf)
    while i < n:
        key, i = _varint(buf, i)
        fn, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        else:  # groups (3/4) don't occur in xplane
            raise ValueError("unsupported wire type %d" % wt)
        out.setdefault(fn, []).append(v)
    return out


def _first_int(f, n, default=0):
    return _signed(f[n][0]) if n in f else default


def _first_str(f, n, default=""):
    return f[n][0].decode("utf-8", "replace") if n in f else default


# ---------------------------------------------------------------------------
# XPlane schema (field numbers per xplane.proto)
# ---------------------------------------------------------------------------

class Event:
    __slots__ = ("name", "offset_ps", "duration_ps", "stats")

    def __init__(self, name, offset_ps, duration_ps, stats):
        self.name = name
        self.offset_ps = offset_ps
        self.duration_ps = duration_ps
        self.stats = stats          # {stat name: value}

    def __repr__(self):
        return "Event(%r, dur=%dps)" % (self.name, self.duration_ps)


class Line:
    __slots__ = ("name", "timestamp_ns", "events")

    def __init__(self, name, timestamp_ns, events):
        self.name = name
        self.timestamp_ns = timestamp_ns
        self.events = events

    def __repr__(self):
        return "Line(%r, %d events)" % (self.name, len(self.events))


class Plane:
    __slots__ = ("name", "lines", "event_metadata", "stat_metadata")

    def __init__(self, name, lines, event_metadata, stat_metadata):
        self.name = name
        self.lines = lines
        self.event_metadata = event_metadata    # id -> (name, {stat: val})
        self.stat_metadata = stat_metadata      # id -> name

    def __repr__(self):
        return "Plane(%r, %d lines)" % (self.name, len(self.lines))


def _parse_stat(buf, stat_meta):
    f = _fields(buf)
    name = stat_meta.get(_first_int(f, 1), "?")
    if 2 in f:          # double
        import struct
        val = struct.unpack("<d", f[2][0])[0]
    elif 3 in f:        # uint64
        val = f[3][0] if isinstance(f[3][0], int) else 0
    elif 4 in f:        # int64
        val = _signed(f[4][0])
    elif 5 in f:        # str
        val = f[5][0].decode("utf-8", "replace")
    elif 6 in f:        # bytes
        val = f[6][0]
    elif 7 in f:        # ref to stat_metadata (interned string)
        val = stat_meta.get(f[7][0], f[7][0])
    else:
        val = None
    return name, val


def _parse_plane(buf):
    f = _fields(buf)
    name = _first_str(f, 2)
    stat_meta = {}
    for entry in f.get(5, ()):
        ef = _fields(entry)
        if 2 in ef:
            mf = _fields(ef[2][0])
            stat_meta[_first_int(mf, 1)] = _first_str(mf, 2)
    event_meta = {}
    for entry in f.get(4, ()):
        ef = _fields(entry)
        if 2 not in ef:
            continue
        mf = _fields(ef[2][0])
        mid = _first_int(mf, 1)
        mname = _first_str(mf, 4) or _first_str(mf, 2)
        mstats = dict(_parse_stat(s, stat_meta) for s in mf.get(5, ()))
        event_meta[mid] = (mname, mstats)
    lines = []
    for lbuf in f.get(3, ()):
        lf = _fields(lbuf)
        lname = _first_str(lf, 11) or _first_str(lf, 2)
        ts = _first_int(lf, 3)
        events = []
        for ebuf in lf.get(4, ()):
            ef = _fields(ebuf)
            mid = _first_int(ef, 1)
            mname, mstats = event_meta.get(mid, ("?", {}))
            stats = dict(mstats)
            for sbuf in ef.get(4, ()):
                k, v = _parse_stat(sbuf, stat_meta)
                stats[k] = v
            events.append(Event(mname, _first_int(ef, 2),
                                _first_int(ef, 3), stats))
        lines.append(Line(lname, ts, events))
    return Plane(name, lines, event_meta, stat_meta)


def parse_xspace(path):
    """Parse one ``.xplane.pb`` file into a list of :class:`Plane`."""
    with open(path, "rb") as fh:
        data = fh.read()
    return [_parse_plane(b) for b in _fields(data).get(1, ())]


def find_xplane_files(logdir):
    """Locate ``*.xplane.pb`` under a jax.profiler logdir (newest run)."""
    if os.path.isfile(logdir):
        return [logdir]
    runs = os.path.join(logdir, "plugins", "profile")
    if not os.path.isdir(runs):
        runs = logdir
    by_dir = {}
    for root, _dirs, files in os.walk(runs):
        for fn in files:
            if fn.endswith(".xplane.pb"):
                by_dir.setdefault(root, []).append(os.path.join(root, fn))
    if not by_dir:
        return []
    # newest run directory wins; every host's file in that run is returned
    newest = max(by_dir, key=lambda d: max(os.path.getmtime(p)
                                           for p in by_dir[d]))
    return sorted(by_dir[newest])


# ---------------------------------------------------------------------------
# Aggregation
# ---------------------------------------------------------------------------

_INSTANCE_RE = re.compile(r"[._-]?\d+$")


def _agg_key(name, stats, by):
    if by == "category":
        return stats.get("hlo_category") or _INSTANCE_RE.sub("", name) or name
    if by == "op":
        # strip the SSA instance suffix: fusion.123 -> fusion
        return _INSTANCE_RE.sub("", name) or name
    if by == "instance":
        return name
    raise ValueError("by must be 'op', 'instance' or 'category', got %r" % by)


def op_table(logdir, line_filter=None, by="op", device_only=True):
    """Aggregate device time per op from a captured trace.

    Parameters
    ----------
    logdir : str
        ``jax.profiler`` log directory (or one ``.xplane.pb`` path).
    line_filter : str, optional
        Only aggregate lines whose name contains this substring
        (e.g. ``"XLA Ops"``).  Default: every line on the chosen planes.
    by : {"op", "instance", "category"}
        Grouping key — base op name (``fusion``), full instance name
        (``fusion.123``), or HLO category.
    device_only : bool
        Restrict to device planes (``/device:...``).  Falls back to host
        planes when the trace contains no device plane (pure-CPU runs).

    Returns
    -------
    dict mapping group key -> dict(count, total_ps, min_ps, max_ps, stats)
    """
    files = find_xplane_files(logdir)
    if not files:
        raise FileNotFoundError("no .xplane.pb under %r" % logdir)
    planes = []
    for p in files:
        planes.extend(parse_xspace(p))
    dev = [p for p in planes if "/device:" in p.name]
    if not dev and device_only:
        # pure-host capture: the busiest host line is the best signal
        dev = [p for p in planes if p.name.startswith("/host:")
               and any(l.events for l in p.lines)]
    host_fallback = device_only and not any("/device:" in p.name for p in dev)
    table = {}
    considered = dev if device_only else planes
    # exact-name preference is GLOBAL: deciding per plane would let a
    # plane lacking the exact line fall back to substring matching and
    # mix async DMA spans into an otherwise compute-only table
    exact = bool(line_filter) and any(
        l.name == line_filter for p in considered for l in p.lines)
    for plane in considered:
        # hierarchical lines overlap ('XLA Modules' events span their
        # 'XLA Ops' children): summing every line double-counts device
        # time.  With no explicit filter, restrict a device plane to its
        # per-op line when one exists.
        # prefer EXACT line-name matches: the sync "XLA Ops" line is the
        # serialized TensorCore timeline, while "Async XLA Ops" carries
        # overlapping DMA spans — substring-matching both silently
        # inflates the table with copy durations that overlap compute
        default_lines = None
        if not line_filter:
            ops_lines = [l for l in plane.lines if l.name == "XLA Ops"] \
                or [l for l in plane.lines if "XLA Ops" in l.name]
            if ops_lines:
                default_lines = {id(l) for l in ops_lines}
        for line in plane.lines:
            if line_filter and (line.name != line_filter if exact
                                else line_filter not in line.name):
                continue
            if default_lines is not None and id(line) not in default_lines:
                continue
            # the host 'python' line is a nested call-stack (inclusive,
            # overlapping durations) — useless as an op table
            if host_fallback and not line_filter and line.name == "python":
                continue
            for ev in line.events:
                key = _agg_key(ev.name, ev.stats, by)
                rec = table.get(key)
                d = ev.duration_ps
                if rec is None:
                    table[key] = {"count": 1, "total_ps": d, "min_ps": d,
                                  "max_ps": d, "stats": dict(ev.stats)}
                else:
                    rec["count"] += 1
                    rec["total_ps"] += d
                    rec["min_ps"] = min(rec["min_ps"], d)
                    rec["max_ps"] = max(rec["max_ps"], d)
    return table


def dumps(logdir, line_filter=None, by="op", top=40, total_label=None):
    """Render the per-op device-time table (reference
    ``AggregateStats::DumpTable`` shape, but with device time)."""
    table = op_table(logdir, line_filter=line_filter, by=by)
    if not table:
        return "(no events)\n"
    grand = sum(r["total_ps"] for r in table.values()) or 1
    hdr = ("%-44s %10s %12s %8s %12s" %
           ("Name", "Count", "Total (ms)", "Share", "Avg (us)"))
    out = ["Device-time per-%s table (%s)." % (by, total_label or logdir),
           "", hdr, "-" * len(hdr)]
    for key in sorted(table, key=lambda k: -table[k]["total_ps"])[:top]:
        r = table[key]
        out.append("%-44s %10d %12.3f %7.1f%% %12.2f"
                   % (key[:44], r["count"], r["total_ps"] / 1e9,
                      100.0 * r["total_ps"] / grand,
                      r["total_ps"] / r["count"] / 1e6))
    out.append("-" * len(hdr))
    out.append("%-44s %10s %12.3f" % ("TOTAL", "", grand / 1e9))
    return "\n".join(out) + "\n"


def save_json(logdir, path, line_filter=None, by="op"):
    table = op_table(logdir, line_filter=line_filter, by=by)
    with open(path, "w") as fh:
        json.dump(table, fh, indent=1, default=repr)


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("logdir")
    ap.add_argument("--line", default=None,
                    help="only lines containing this substring (e.g. 'XLA Ops')")
    ap.add_argument("--by", default="op",
                    choices=["op", "instance", "category"])
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--json", default=None, help="also dump JSON here")
    args = ap.parse_args(argv)
    print(dumps(args.logdir, line_filter=args.line, by=args.by,
                top=args.top), end="")
    if args.json:
        save_json(args.logdir, args.json, line_filter=args.line, by=args.by)


if __name__ == "__main__":   # pragma: no cover
    main()
