"""The benchmark's command:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process runs one cell once: it builds the cell from the seed, warms up
the cell's own shapes (set-up), measures for ``--seconds``, checks what the
timed object produced against the plain reference and prints one JSON line.
Everything that belongs to one configuration, mix, entry or metric is a file
found by its name in ``BENCHMARK.json`` (see ``benchmark/README.md``); this
file knows none of them.
"""
import time

T_START = time.perf_counter()   # before the heavy imports: set-up counts them

import argparse            # noqa: E402
import importlib.util      # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import shutil              # noqa: E402
import sys                 # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def say(text):
    """A line of the run's own account, on standard error."""
    print("bench: " + text, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """The file ``benchmark/<kind>/<name>.py`` as a module; None where there
    is no such file."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "benchmark.%s.%s" % (kind, name.replace(".", "_")), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Run:
    """What an entry and the metric readers are given."""

    def __init__(self, args, manifest):
        cells = {c["name"]: c for c in manifest["workloads"]}
        if args.workload not in cells:
            raise SystemExit("no workload %r in BENCHMARK.json (has %s)"
                             % (args.workload, sorted(cells)))
        self.manifest = manifest
        self.cell = cells[args.workload]
        configs = {c["name"]: c for c in manifest["configs"]}
        self.config = load_json(configs[self.cell["config"]]["file"])
        self.traffic = load_json("benchmark", "traffic",
                                 self.cell["traffic"] + ".json")
        self.limits = load_json("benchmark", "limits",
                                self.cell["name"] + ".json")
        self.seed, self.seconds = args.seed, args.seconds
        self.trace = bool(args.trace)
        self.rehearsal = args.rehearse is not None
        if self.rehearsal:
            for part, doc in json.loads(args.rehearse).items():
                getattr(self, part).update(doc)
        self.trace_dir = os.path.join(
            ROOT, ".bench_work", self.cell["name"], "trace")
        self.load, self.log = load_module, say
        self.device = self.peaks = None
        self.result = self.trace_data = None
        self.setup_s = self.memory_peak_bytes = None


def find_device(run):
    """The device as jax reports it; exits unless it is a TPU with the chips
    the cell asks for (a rehearsal takes what there is)."""
    import jax
    devices = jax.devices()
    desc = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    say("device: %s" % json.dumps(desc))
    if not run.rehearsal:
        if desc["platform"] != "tpu" or desc["count"] < run.cell["chips"]:
            say("this cell needs %d TPU chip(s); no result" %
                run.cell["chips"])
            raise SystemExit(3)
    peaks = load_json("benchmark", "peaks.json")
    if desc["kind"] not in peaks:
        if not run.rehearsal:
            say("device kind %r is not in benchmark/peaks.json" % desc["kind"])
            raise SystemExit(3)
    run.peaks = peaks.get(desc["kind"])
    return desc


class CacheEvents:
    """jax's own persistent-cache hit and miss events."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory_peak(chips):
    """(peak bytes on the fullest chip, the allocator's part, the programs'
    part, the site of that program).

    `memory_stats()["peak_bytes_in_use"]` on this runtime counts the arrays
    that are alive and leaves out the temporaries of a program while it runs
    (PERF.md section 5: a step whose executable asks 8 GiB of temporaries
    left the allocator's peak where the seeded pool had put it). The peak is
    therefore the allocator's peak plus the largest temporaries among the
    programs this process ran, as XLA's `memory_analysis()` gives them for
    each executable: the program publishes that figure for every site it
    compiles (`xla_stats.ledger()`, section ``xla_temp``)."""
    import jax
    from mxnet_tpu import xla_stats
    live = 0
    for device in jax.devices()[:chips]:
        stats = device.memory_stats() or {}
        live = max(live, int(stats.get("peak_bytes_in_use", 0)))
    temps = {scope: n for (scope, section), n in xla_stats.ledger().items()
             if section == "xla_temp"}
    site = max(temps, key=temps.get) if temps else None
    temp = temps[site] if site else 0
    return live + temp, live, temp, site


def say_intervals(times, keep=None):
    """How the window's intervals between callbacks lie, for whoever asks
    why a tail moved; ``keep`` names a file that gets them all."""
    gaps = sorted((b - a) * 1e3 for a, b in zip(times, times[1:]))
    if len(gaps) < 20:
        return
    at = {q: gaps[min(len(gaps) - 1, int(q * len(gaps) / 100))]
          for q in (50, 75, 90, 95, 99)}
    say("intervals between callbacks, ms: " + ", ".join(
        "p%d %.2f" % (q, v) for q, v in at.items())
        + ", max %.2f; %d of %d over 1.1 x the median"
        % (gaps[-1], sum(g > 1.1 * at[50] for g in gaps), len(gaps)))
    if keep:
        with open(keep, "w") as f:
            json.dump([(b - a) * 1e3 for a, b in zip(times, times[1:])], f)


def read_metrics(run, kind, entries):
    """{name: {"value", "unit"}} of the manifest's ``entries`` that list
    this cell (or list none) and whose reader finds something to read."""
    out = {}
    for entry in entries:
        if run.cell["name"] not in entry.get("workloads",
                                             [run.cell["name"]]):
            continue
        reader = load_module(kind, entry["name"])
        if reader is None:
            raise SystemExit("no reader benchmark/%s/%s.py"
                             % (kind, entry["name"]))
        value = reader.read(run)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", help=(
        "JSON {'config': {...}, 'traffic': {...}} laid over the cell's "
        "files, for a CPU rehearsal at a tiny size: the only way past the "
        "look for a TPU, and never a measurement"))
    parser.add_argument("--keep-trace", help=(
        "copy the traced run's .xplane.pb to this path, for a hand look"))
    parser.add_argument("--keep-intervals", help=(
        "write the window's intervals between callbacks, in ms, to this "
        "path as one JSON list, for a hand look"))
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "mxnet_tpu")):
        say("the program (mxnet_tpu/) is not in this checkout; no result")
        return 3
    run = Run(args, load_json("BENCHMARK.json"))
    say("cell %s: configuration %s under %s on %d chip(s), seed %d, %g s%s"
        % (run.cell["name"], run.cell["config"], run.cell["traffic"],
           run.cell["chips"], run.seed, run.seconds,
           ", traced" if run.trace else ""))

    device = find_device(run)
    from mxnet_tpu.compiled import enable_compile_cache
    cache = CacheEvents()
    say("compile cache at %s" % enable_compile_cache())
    if run.trace:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        os.makedirs(run.trace_dir)

    entry = load_module("entries", run.traffic["entry"])
    result = run.result = entry.run(run)
    setup_hits, setup_misses = cache.hits, cache.misses
    run.setup_s = result["t_open"] - T_START
    run.memory_peak_bytes, live, temp, site = memory_peak(run.cell["chips"])
    window_s = result["t_close"] - result["t_open"]
    say("set-up %.2f s; compile cache %d hits, %d misses in the whole run"
        % (run.setup_s, setup_hits, setup_misses))
    say("window: %d batches of %d rows in %.3f s, closed %.3f s after the "
        "%g s asked; program counters over it %s"
        % (result["batches"], result["rows_per_batch"], window_s,
           window_s - run.seconds, run.seconds,
           json.dumps(result["counters"])))
    say_intervals(result["callback_times"], args.keep_intervals)
    say("memory: peak %.3f GiB on the fullest chip = %.3f GiB of live arrays "
        "at the allocator's peak + %.3f GiB of temporaries of the program "
        "at site %s" % (run.memory_peak_bytes / 2**30, live / 2**30,
                        temp / 2**30, site))

    device["memory_peak_bytes"] = run.memory_peak_bytes
    line = {"correct": False, "attempted": result["batches"],
            "failed": 0, "metrics": {}, "device": device}
    if run.trace:
        from benchmark import reduce
        t0 = time.perf_counter()
        try:
            xplane = reduce.find_xplane(run.trace_dir)
            if args.keep_trace:
                shutil.copy(xplane, args.keep_trace)
            run.trace_data = reduce.reduce(xplane,
                                           run.traffic["step_program"])
        except FileNotFoundError as e:
            say("trace: %s" % e)
        if run.trace_data is not None:
            trace = run.trace_data
            device["busy_s"], device["window_s"] = \
                trace["busy_s"], trace["window_s"]
            line["breakdown"] = reduce.breakdown(trace)
            say("trace: %d dispatches of the step program in %.3f s, device "
                "busy %.3f s; read in %.1f s"
                % (trace["dispatches"], trace["window_s"], trace["busy_s"],
                   time.perf_counter() - t0))
        line["metrics"] = read_metrics(run, "layer_metrics",
                                       run.manifest["per_layer"])
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    else:
        line["metrics"] = read_metrics(run, "end_to_end",
                                       run.manifest["end_to_end"])
    for name, m in line["metrics"].items():
        say("metric %s = %r %s" % (name, m["value"], m["unit"]))

    # the comparison, last: the peak is read and the program's state is gone
    from benchmark import compare
    t0 = time.perf_counter()
    reference_side = result.pop("reference_side")
    ref, w0 = reference_side()
    values = compare.numbers(result["program"], ref, w0, result["optimizer"])
    correct, table, lines = compare.judge(values, run.limits["limits"])
    say("reference: followed %d steps in %.1f s (compile cache now %d hits, "
        "%d misses)" % (len(ref["losses"]), time.perf_counter() - t0,
                        cache.hits, cache.misses))
    line["correct"] = bool(correct)
    line["failed"] = 0 if correct else result["batches"]
    line["compared"] = table
    for text in lines:
        say(text)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
