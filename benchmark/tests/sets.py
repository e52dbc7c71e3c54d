"""Runs of a cell as the driver makes them, one process each, the result
lines gathered in one file:

    python -m benchmark.tests.sets --workload <cell> --seeds 1,2,3 \
        --seconds 40 --trace 0 --out chiprun_out/sets/<name>.jsonl

For the builder's two sets of runs on the chip; the benchmark's own runs
never call it."""
import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        for seed in args.seeds.split(","):
            t0 = time.time()
            done = subprocess.run(
                manifest["command"] + [
                    "--workload", args.workload, "--seed", seed, "--seconds",
                    "%g" % args.seconds, "--trace", str(args.trace)],
                cwd=ROOT, capture_output=True, text=True)
            lines = done.stdout.strip().splitlines()
            doc = {"workload": args.workload, "seed": int(seed),
                   "trace": args.trace, "rc": done.returncode,
                   "wall_s": time.time() - t0,
                   "stderr": [l for l in done.stderr.splitlines()
                              if l.startswith("bench:")][-30:]}
            try:
                doc["line"] = json.loads(lines[-1])
            except (IndexError, ValueError):
                doc["stdout_tail"] = done.stdout[-2000:]
                doc["stderr_tail"] = done.stderr[-3000:]
            out.write(json.dumps(doc) + "\n")
            out.flush()
            print("%s seed %s rc %d %.0f s correct %s" % (
                args.workload, seed, done.returncode, doc["wall_s"],
                doc.get("line", {}).get("correct")), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
