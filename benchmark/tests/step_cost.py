"""What the program's own step records cost the `fit` loop's host side:

    python -m benchmark.tests.step_cost [--root <checkout>] [--steps 2000]

One `stepprof.step()` with the four phases a `Module.fit` step has (`h2d`
with its byte count, `dispatch`, `data_wait`, `device_compute
via=update_metric`) and `_count_fit_batch`'s two counters, around nothing,
so the time is the instruments' own: runprof's ledger, memprof's sample
and the flight recorder's tap included, as in a run. Prints one JSON line:
microseconds a step, median and quartiles over ``--repeats`` loops.
``--root`` takes the program from another checkout (the parent commit
unpacked beside this one), for the same loop on both. No device is
touched; the number is the host's, whichever machine runs it."""
import argparse
import json
import os
import statistics
import sys
import time


def loop(steps):
    from mxnet_tpu import stepprof
    from mxnet_tpu.module.base_module import _count_fit_batch

    class Batch:
        data = [type("A", (), {"shape": (256, 224, 224, 3)})()]

    batch = Batch()
    t0 = time.perf_counter()
    for _ in range(steps):
        with stepprof.step():
            with stepprof.phase("h2d") as ph:
                ph["bytes"] = 77070336
            with stepprof.phase("dispatch", site="module.fused_step"):
                pass
            with stepprof.phase("data_wait"):
                pass
            with stepprof.phase("device_compute", via="update_metric"):
                pass
            _count_fit_batch(batch)
    return (time.perf_counter() - t0) / steps * 1e6


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    parser.add_argument("--steps", type=int, default=2000)
    parser.add_argument("--repeats", type=int, default=9)
    args = parser.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.root))
    from mxnet_tpu import xla_stats  # noqa: F401  (its import installs
    # the flight recorder's tap, which every span of a run then pays)
    loop(200)                     # imports, first histograms
    us = sorted(loop(args.steps) for _ in range(args.repeats))
    q1, q2, q3 = statistics.quantiles(us, n=4)
    import mxnet_tpu
    print(json.dumps({"program": os.path.dirname(mxnet_tpu.__file__),
                      "steps": args.steps, "repeats": args.repeats,
                      "us_per_step": {"p25": q1, "p50": q2, "p75": q3,
                                      "min": us[0], "max": us[-1]}}))


if __name__ == "__main__":
    main()
