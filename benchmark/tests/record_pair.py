"""Record the pair `test_timeline.py` holds the join to: one traced run of
`vgg16_fit` at the tiny size (`tiny.py`) on the chip, its `.xplane.pb` and
the program's `stepprof.timeline()` around the profile, as JSON:

    chiprun -- python -m benchmark.tests.record_pair --out chiprun_out/tiny_pair

writes ``<out>.xplane.pb`` and ``<out>.timeline.json`` (``steps``, the
trace's ``profile_start_time`` and the ``idle_share`` the run printed), to
be copied to ``benchmark/tests/data/``. The window is 2 s, so that the ring
of 512 steps still holds the traced stretch when the run ends. With
``--workload <cell>`` it keeps the same pair of a cell at its own size
(``--seconds 40``), for a hand look at the join."""
import argparse
import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import run as harness, timeline  # noqa: E402
from benchmark.tests import tiny  # noqa: E402


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", required=True)
    parser.add_argument("--seed", type=int, default=2147489003)
    parser.add_argument("--seconds", type=float, default=2.0)
    parser.add_argument("--workload", help="a cell at its own size, in "
                        "place of %s at the tiny size" % tiny.CELL)
    args = parser.parse_args(argv)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    trace = args.out + ".xplane.pb"
    # on the chip `step.mfu` finds a peak for bfloat16 alone
    overlay = json.loads(tiny.overlay())
    overlay["config"]["dtype"] = "bfloat16"
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        code = harness.main(
            ["--workload", args.workload or tiny.CELL, "--seed",
             str(args.seed), "--seconds", str(args.seconds), "--trace", "1",
             "--keep-trace", trace]
            + ([] if args.workload else ["--rehearse", json.dumps(overlay)]))
    line = json.loads(printed.getvalue().strip().splitlines()[-1])
    print(json.dumps(line))
    start, stop = timeline.profile_times(trace)
    # the steps from a little before the profile's start to a little after
    # its stop: the join needs the stretch covered, the test nothing more
    steps = [s for s in timeline.program_timeline() if s["clock"]
             and start - 50e6 <= s["clock"][0] <= stop + 50e6]
    shares = {name[len("idle."):-len("_share")]: m["value"]
              for name, m in line["metrics"].items()
              if name.startswith("idle.")}
    with open(args.out + ".timeline.json", "w") as f:
        json.dump({"device": line["device"], "profile_start_time": start,
                   "idle_share": shares, "steps": steps}, f)
    harness.say("kept %d steps around the profile, idle shares %s"
                % (len(steps), json.dumps(shares)))
    return code


if __name__ == "__main__":
    sys.exit(main())
