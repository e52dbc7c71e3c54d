"""The host's side of a traced run, laid on the device trace's clock: every
idle nanosecond of the traced stretch gets the name of what the host was
doing. `reduce.py` sees device events only (its head says why the host
tracer stays off); the program keeps its own account of each `fit` step in
memory, `mxnet_tpu.stepprof.timeline()`: a step's number, one clock pair
read back to back at its entry (`time.time_ns()`, `time.perf_counter()`)
and its phases in order as (name, start offset, duration, attrs). This file
reads that after the window, joins it to the trace, and the readers
`layer_metrics/fit.*_ms`, `dispatch.host_ms`, `idle.*_share` and
`timeline.unmatched_steps` read the join.

**The clocks.** A device event's `start_ns` in the `.xplane.pb` counts from
the start of the profile, and the trace says when that was on the host's
wall clock: the plane ``Task Environment`` carries the stats
``profile_start_time`` / ``profile_stop_time`` in Unix-epoch nanoseconds
(the profiler session's own `GetCurrentTimeNanos()`, the clock
`time.time_ns()` reads). So an instant ``t`` on `perf_counter` inside a
step with the pair ``(wall_ns, perf)`` lies at

    wall_ns + (t - perf) * 1e9 - profile_start_time

device nanoseconds. No offset is fitted from the gaps that are being
attributed. What holds the two zeros together is checked in every traced
run (`proof` below, printed on standard error) and counted:
``timeline.unmatched_steps`` must read 0. The readings on the chip are in
PERF.md, section 5.

**The buckets.** Each idle instant of the first chip between the start of
the first execution of the step program and the start of the last (the
stretch `reduce.reduce` delimits) goes into exactly one of:

    enqueued   after the enqueue instant of the NEXT execution of the step
               program (the end of its step's `dispatch` phase: the
               compiled call has returned) and before that execution
               starts on the device: the host has handed the program over
               and the chip has not begun, so it waits for an operand,
               which in `fit` is the staged batch still in flight
    stage      the host is in `h2d` (`Module._load_batch`, `stack_batches`)
    dispatch   in `dispatch` (gather, `FusedApplier.prepare`, the compiled
               call until it returns)
    data_wait  in `data_wait` (the iterator)
    readback   in `device_compute via=update_metric` with the device
               already idle: the copy of the outputs to the host and the
               metric's arithmetic
    other      none of these: the loop's own Python, the counters, the
               callbacks, the seam between two steps

A timeline that does not reach back to the traced stretch (the ring holds
`MXNET_STEPPROF_WINDOW` steps, 512) gives `None`, never a guess; so does a
program without `stepprof.timeline` (the parent of the PR that brought
it)."""
import bisect
import re
import statistics

from benchmark import reduce

BUCKETS = ("enqueued", "stage", "dispatch", "data_wait", "readback", "other")
TASK_PLANE = "Task Environment"

# reader name -> key of `per_batch_ms`
PER_BATCH = {"fit.stage_ms": "stage", "fit.data_wait_ms": "data_wait",
             "dispatch.host_ms": "dispatch",
             "fit.readback_wait_ms": "readback", "fit.other_ms": "other"}


def bucket_of(name, attrs):
    """The bucket of a host phase of `stepprof`'s taxonomy."""
    if name == "h2d":
        return "stage"
    if name in ("dispatch", "data_wait"):
        return name
    if name == "device_compute" and attrs.get("via") == "update_metric":
        return "readback"
    return "other"


def program_timeline():
    """`stepprof.timeline()` of this process; None where the program has
    none."""
    try:
        from mxnet_tpu import stepprof
        return stepprof.timeline()
    except (ImportError, AttributeError):
        return None


def window_steps(steps, t_open, t_close):
    """The records that have a clock and were entered inside
    [t_open, t_close] on `perf_counter`."""
    return [s for s in steps
            if s.get("clock") and t_open <= s["clock"][1] <= t_close]


def per_batch_ms(steps):
    """{bucket: median over ``steps`` of that phase's time a batch, ms} for
    the four host phases, and ``other``: the step's wall less those four,
    a batch, so that the five tile a step. None for no steps."""
    if not steps:
        return None
    rows = {b: [] for b in BUCKETS[1:]}
    for step in steps:
        spent = dict.fromkeys(BUCKETS[1:-1], 0.0)
        for name, _, dur, attrs in step["spans"]:
            bucket = bucket_of(name, attrs)
            if bucket != "other":
                spent[bucket] += dur
        spent["other"] = step["wall"] - sum(spent.values())
        for bucket, seconds in spent.items():
            rows[bucket].append(seconds / step["batches"] * 1e3)
    return {b: statistics.median(v) for b, v in rows.items()}


def profile_times(path):
    """(profile_start_time, profile_stop_time) of the trace at ``path``, in
    Unix nanoseconds; None where it carries none."""
    for plane in reduce.load(path).planes:
        if plane.name == TASK_PLANE:
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                return (int(stats["profile_start_time"]),
                        int(stats.get("profile_stop_time", 0)))
    return None


def place(step, zero_ns):
    """A step record on the device's line: ``entry``, ``end`` and every
    span's (start, end, bucket, phase name) in nanoseconds since the start
    of the profile (``zero_ns``, Unix)."""
    wall_ns, _ = step["clock"]
    base = wall_ns - zero_ns   # whole numbers: no float near 1.8e18

    def at(offset_s):
        return base + offset_s * 1e9

    return {"seq": step["seq"], "entry": float(base),
            "end": at(step["wall"]),
            "spans": [(at(start), at(start + dur), bucket_of(name, attrs),
                       name) for name, start, dur, attrs in step["spans"]]}


def host_line(steps):
    """The placed ``steps`` (in order of entry) as one labelled line:
    (edges, labels) with ``labels[i]`` the bucket over
    [edges[i], edges[i+1]). Whatever no phase covers between the first
    entry and the last end is ``other``; a phase that starts inside
    another yields to it."""
    edges, labels = [steps[0]["entry"]], []

    def cover(until, label):
        if until <= edges[-1]:
            return
        if labels and labels[-1] == label:
            edges[-1] = until
        else:
            labels.append(label)
            edges.append(until)

    for step in steps:
        cover(step["entry"], "other")
        for start, end, bucket, _ in sorted(step["spans"]):
            cover(start, "other")
            cover(end, bucket)
        cover(step["end"], "other")
    # edges[0] opens the line; edges[i + 1] closes labels[i]
    return edges, labels


def spread(a, b, edges, labels, into):
    """Add the nanoseconds of [a, b] to ``into`` by the line's labels."""
    i = max(bisect.bisect_right(edges, a) - 1, 0)
    while a < b and i < len(labels):
        until = min(b, edges[i + 1])
        if until > a:
            into[labels[i]] += until - a
            a = until
        i += 1


def attribute(steps, trace):
    """Join placed ``steps`` (`place`, in order of entry) with ``trace``
    (`reduce.reduce`). Returns None where the steps do not cover the traced
    stretch, else a dict:

    idle_ns     {bucket: nanoseconds} over the first chip's gaps; the six
                sum to the gaps' total
    unmatched   executions of the step program that start before their own
                step's `dispatch` phase does, or end after their own
                step's last `device_compute` phase has ended, plus the
                difference between the steps entered inside the stretch and
                the trace's dispatches; 0 when the clocks agree
    proof       what `say_proof` prints
    An execution's own step is the last one entered before it starts."""
    chip = trace["chips"][0]
    rx = re.compile(trace["step_program"])
    runs = sorted((m["start"], m["end"]) for m in chip["modules"]
                  if rx.search(m["name"]))
    # the execution that starts where the stretch ends was clipped away:
    # only its start is known, and only its enqueue instant is needed
    runs.append((trace["hi"], None))
    entries = [s["entry"] for s in steps]
    if not steps or entries[0] > trace["lo"] or steps[-1]["end"] < trace["hi"]:
        return None

    enqueued, early, late, tails, waits = [], 0, 0, [], []
    for start, end in runs:
        own = steps[bisect.bisect_right(entries, start) - 1]
        calls = [sp for sp in own["spans"]
                 if sp[3] == "dispatch" and sp[0] <= start]
        if not calls:
            early += 1
            continue
        handed = max(calls)[1]   # the enqueue instant
        if handed < start:
            enqueued.append((handed, start))
        waits.append(start - handed)
        if end is None:
            continue
        reads = [sp[1] for sp in own["spans"] if sp[3] == "device_compute"]
        if not reads or end > max(reads):
            late += 1
        if reads:
            tails.append(max(reads) - end)
    inside = sum(trace["lo"] <= e < trace["hi"] for e in entries)

    edges, labels = host_line(steps)
    idle = dict.fromkeys(BUCKETS, 0.0)
    starts = [a for a, _ in enqueued]
    for a, b in chip["gaps"]:
        # the part of the gap that lies in an enqueued interval first; the
        # rest by what the host was in
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(enqueued) and enqueued[i][0] < b:
            lo, hi = max(a, enqueued[i][0]), min(b, enqueued[i][1])
            if hi > lo:
                spread(a, lo, edges, labels, idle)
                idle["enqueued"] += hi - lo
                a = hi
            i += 1
        spread(a, b, edges, labels, idle)
    return {"idle_ns": idle,
            "unmatched": early + late + abs(inside - trace["dispatches"]),
            "proof": {"executions": len(runs) - 1, "steps_inside": inside,
                      "before_dispatch": early, "after_readback": late,
                      "readback_tail_ns": tails, "enqueued_wait_ns": waits}}


def _spread_of(values):
    if not values:
        return "none"
    ms = sorted(v / 1e6 for v in values)
    return "min %.3f, median %.3f, max %.3f ms over %d" % (
        ms[0], statistics.median(ms), ms[-1], len(ms))


def say_proof(log, joined, steps, times):
    """The clock's proof, on standard error: the counts behind
    `timeline.unmatched_steps`, the end of each step's read-back wait less
    the device end of its step program (a small positive constant when the
    two zeros coincide: the outputs coming back), and where the profile's
    own start and stop fall among the steps (the tracer starts and stops
    the profiler inside a batch-end callback, after the step's last
    phase: off by a whole step, the periodic checks above would not see
    it, this does)."""
    proof = joined["proof"]
    log("timeline: %d executions of the step program against %d steps "
        "entered in the stretch; %d start before their step's dispatch, %d "
        "end after their step's last read-back wait"
        % (proof["executions"], proof["steps_inside"],
           proof["before_dispatch"], proof["after_readback"]))
    log("timeline: end of the read-back wait less device end of the step "
        "program: %s; device start less enqueue instant: %s"
        % (_spread_of(proof["readback_tail_ns"]),
           _spread_of(proof["enqueued_wait_ns"])))
    entries = [s["entry"] for s in steps]
    marks = [("start", 0.0)]
    if times[1]:
        marks.append(("stop", float(times[1] - times[0])))
    for what, t in marks:
        i = bisect.bisect_right(entries, t) - 1
        if i < 0 or t > steps[i]["end"]:
            log("timeline: the profile's %s lies in no step" % what)
            continue
        last = max([sp[1] for sp in steps[i]["spans"]] or [steps[i]["entry"]])
        log("timeline: the profile's %s lies in step %d, %.3f ms after its "
            "last phase ended and %.3f ms before the step did"
            % (what, steps[i]["seq"], (t - last) / 1e6,
               (steps[i]["end"] - t) / 1e6))


def join(run):
    """The join of this run, made once and kept on ``run``:
    {"per_batch_ms": ..., "idle_share": {bucket: %} or None,
    "unmatched": count or None}; None where the program keeps no
    timeline."""
    if not hasattr(run, "timeline_join"):
        run.timeline_join = _join(run)
    return run.timeline_join


def _join(run):
    steps = program_timeline()
    if steps is None:
        return None
    steps = window_steps(steps, run.result["t_open"], run.result["t_close"])
    out = {"per_batch_ms": per_batch_ms(steps), "idle_share": None,
           "unmatched": None}
    trace = run.trace_data
    if trace is None or not steps:
        return out
    try:
        times = profile_times(reduce.find_xplane(run.trace_dir))
    except FileNotFoundError:
        times = None
    if times is None:
        run.log("timeline: the trace names no profile_start_time")
        return out
    placed = [place(s, times[0]) for s in steps]
    joined = attribute(placed, trace)
    if joined is None:
        run.log("timeline: %d steps from seq %d do not reach back to the "
                "traced stretch" % (len(steps), steps[0]["seq"]))
        return out
    say_proof(run.log, joined, placed, times)
    stretch_ns = trace["window_s"] * 1e9
    out["idle_share"] = {b: ns / stretch_ns * 100.0
                         for b, ns in joined["idle_ns"].items()}
    out["unmatched"] = joined["unmatched"]
    return out


def read(run, name):
    """The value of the per-layer metric ``name``, or None."""
    joined = join(run)
    if joined is None:
        return None
    if name == "timeline.unmatched_steps":
        return joined["unmatched"]
    if name in PER_BATCH:
        medians = joined["per_batch_ms"]
        return None if medians is None else medians[PER_BATCH[name]]
    shares = joined["idle_share"]
    bucket = name[len("idle."):-len("_share")]
    return None if shares is None else shares[bucket]
