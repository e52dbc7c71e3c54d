"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer
readers use. Read with `jax.profiler.ProfileData`, which needs nothing but
jax.

The trace holds device events only. With the host tracer on, the runtime
writes one event for every block of the host-side layout change of a staged
batch (6.4 million ``Transpose`` events in seven steps of `resnet50_fit`, a
file of 225 MB, each step slowed from 0.15 s to 2 s; my chip run, PR 23), so
the traced stretch is delimited on the device's own clock instead: from the
start of the first execution of the step program (the mix names it,
``step_program``) to the start of the last, so it holds a whole number of
dispatches. Device events are clipped to it.

What a TPU trace holds (looked at by hand, PERF.md section 5): one plane to
a chip, ``/device:TPU:<n>``, with a line ``XLA Modules`` (one event to an
execution of a compiled program, named ``<module>(<fingerprint>)``) and a
line ``XLA Ops`` (one event to an HLO instruction that ran, with the
instruction, named by its whole HLO text; its stats carry no category, so
an op's kind is read off its name, and a convolution inside a plain
``fusion.N`` cannot be told from the rest)."""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return files[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def union_ns(intervals, lo, hi):
    """Length of the union of ``intervals`` [(start, end), ...] inside
    [lo, hi]."""
    total, edge = 0.0, lo
    for start, end in sorted(intervals):
        start, end = max(start, edge), min(end, hi)
        if end > start:
            total += end - start
            edge = end
    return total


def gaps_ns(intervals, lo, hi):
    """The idle gaps [(start, end), ...] that ``intervals`` leave in
    [lo, hi]."""
    gaps, edge = [], lo
    for start, end in sorted(intervals):
        if start > edge:
            gaps.append((edge, min(start, hi)))
        edge = max(edge, end)
        if edge >= hi:
            break
    if edge < hi:
        gaps.append((edge, hi))
    return [(a, b) for a, b in gaps if b > a]


def short_name(name):
    """``%fusion.12`` of ``%fusion.12 = bf16[...] fusion(...)``: a device op
    event is named by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line):
    out = []
    for e in line.events:
        stats = {}
        for k, v in e.stats:
            stats[k] = v
        out.append({"name": short_name(e.name), "start": float(e.start_ns),
                    "end": float(e.start_ns) + float(e.duration_ns),
                    "stats": stats})
    return out


def category(event):
    """The HLO category of a device op event, lower case; the instruction's
    own name stands in where the trace gives none."""
    for key in ("hlo_category", "category"):
        if key in event["stats"]:
            return str(event["stats"][key]).lower()
    return re.sub(r"[.\d]+$", "", event["name"]).lower()


def reduce(path, step_program):
    """The trace at ``path`` as a dict:

    window_s, dispatches  the delimited stretch and the executions of the
                     step program (regex ``step_program``) that it holds
    chips            one entry to a device plane: busy_s, ops (events
                     clipped to the window, with ``category``), modules,
                     gaps [(start, end)]
    busy_s           mean of the chips' busy_s
    Returns None where the trace holds no device plane or fewer than two
    executions of the step program (a CPU rehearsal): the readers then have
    nothing to read."""
    profile = load(path)
    rx = re.compile(step_program)
    starts = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name) and not starts:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    starts = sorted(float(e.start_ns) for e in line.events
                                    if rx.search(e.name))
    if len(starts) < 2:
        return None
    lo, hi = starts[0], starts[-1]
    chips = []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue

        def clipped(line_name):
            out = []
            for e in _events(lines[line_name]) if line_name in lines else []:
                if e["end"] <= lo or e["start"] >= hi:
                    continue
                e["start"], e["end"] = max(e["start"], lo), min(e["end"], hi)
                e["category"] = category(e)
                out.append(e)
            return out

        ops = clipped(OPS_LINE)
        spans = [(e["start"], e["end"]) for e in ops]
        chips.append({"plane": plane.name,
                      "busy_s": union_ns(spans, lo, hi) / 1e9,
                      "ops": ops, "modules": clipped(MODULES_LINE),
                      "gaps": gaps_ns(spans, lo, hi)})
    if not chips:
        return None
    return {"window_s": (hi - lo) / 1e9, "dispatches": len(starts) - 1,
            "lo": lo, "hi": hi, "chips": chips, "step_program": step_program,
            "busy_s": sum(c["busy_s"] for c in chips) / len(chips)}


def module_time(trace, pattern):
    """(seconds, executions) of the compiled programs whose name matches
    ``pattern`` on the first chip, over the window."""
    rx = re.compile(pattern)
    hits = [m for m in trace["chips"][0]["modules"] if rx.search(m["name"])]
    return sum(m["end"] - m["start"] for m in hits) / 1e9, len(hits)


def breakdown(trace, top=10):
    """The contract's optional ``breakdown``: the device operations that
    took most time (summed by name on the first chip), and the idle time by
    where it falls: between two executions of the step program (the host
    has not dispatched the next one) or inside one. The host's own spans
    are not in the trace (see the head of this file)."""
    chip = trace["chips"][0]
    by_name = {}
    for e in chip["ops"]:
        if e["category"] in ("while", "conditional", "call"):
            continue   # a wrapper: its body's ops are events of their own
        key = "%s [%s]" % (re.sub(r"[.\d]+$", "", e["name"]), e["category"])
        by_name[key] = by_name.get(key, 0.0) + (e["end"] - e["start"]) / 1e9
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    rx = re.compile(trace["step_program"])
    steps = [(m["start"], m["end"]) for m in chip["modules"]
             if rx.search(m["name"])]
    by_cause = {}
    for a, b in chip["gaps"]:
        inside = any(s <= a and b <= t for s, t in steps)
        cause = "inside_step_program" if inside else "between_step_programs"
        by_cause[cause] = by_cause.get(cause, 0.0) + (b - a) / 1e9
    gaps = sorted(by_cause.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def describe(path, top=25):
    """A hand look at a trace: planes, lines, the commonest event names with
    their stats. Printed by ``python -m benchmark.reduce <file>``."""
    profile = load(path)
    out = []
    for plane in profile.planes:
        out.append("PLANE %s" % plane.name)
        for line in plane.lines:
            events = _events(line)
            out.append("  LINE %s: %d events" % (line.name, len(events)))
            total = {}
            for e in events:
                key = re.sub(r"[.\d]+$", "", e["name"])[:80]
                t = total.setdefault(key, [0, 0.0, e])
                t[0] += 1
                t[1] += e["end"] - e["start"]
            for key, (n, ns, e) in sorted(
                    total.items(), key=lambda kv: -kv[1][1])[:top]:
                out.append("    %-60s n=%-6d %.3f ms  stats=%s" % (
                    key, n, ns / 1e6,
                    {k: str(v)[:60] for k, v in list(e["stats"].items())[:8]}))
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
