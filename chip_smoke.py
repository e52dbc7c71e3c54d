#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one chip: phases 1-5 below
    python chip_smoke.py --chips 4   # four chips: the data-parallel phase only

Drives the three front doors once, on the attached TPU, at the full width
of ResNet-50 (depth and weights as the model zoo builds them from a seed)
and of the LSTM-PTB 2x650 language model:

1. device   jax sees a TPU; the context rule; `block_until_ready` blocks
2. train    `Module.fit`, ResNet-50 bf16 NHWC batch 128: the fused `_step`,
            then `_step_scan` with donated params
3. predict  hybridized Gluon ResNet-50 fp32 NCHW batch 32 against eager on
            the chip and against the same net on `mx.cpu()`
4. serve    `python -m mxnet_tpu.serving.server` on the exported net,
            answers compared with phase 3's forward
5. lstm     `Module.fit` of the word-LM, Pallas LSTM kernels in the step,
            their gradients against the `lax.scan` reference

The chip belongs to one process at a time, and phase 4's server is a
process of its own. So this parent never imports jax or mxnet_tpu: it
runs each phase as a child in turn (`--phase NAME`, the parent's own
protocol, not a user's option), passes its output through, and reads the
child's last `PHASE_RESULT {json}` line. A phase that fails, is killed,
times out, or reports another platform than `tpu` ends the run with a
non-zero exit and `"ok": false`; nothing carries on on the CPU.

The last line of standard output is one JSON object:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`.
Times printed on the way carry the word "smoke": they are not benchmark
numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
#: exported model and phase 3's forward, handed from phase 3 to phase 4
WORK = os.path.join(REPO, ".chip_smoke")
RESULT = "PHASE_RESULT "
#: the contract gives 1200 s, compilation included
DEADLINE_S = 1150.0
SEED = 0

#: the sizes the chip run uses: published widths, full depth
FULL = {
    "model": "resnet50_v1", "classes": 1000, "image": 224,
    "train_batch": 128, "train_batches": 30, "scan_k": 30,
    "scan_unroll": 3, "scan_epochs": 3, "predict_batch": 32,
    "serve_max_batch": 8, "serve_sizes": (1, 3, 8, 2),
    "lstm": {"vocab": 10000, "hidden": 650, "layers": 2, "batch": 32,
             "bptt": 35, "batches": 6},
    "dp_steps": 6, "burn_n": 4096, "burn_iters": 300,
}


class SmokeFailure(Exception):
    """A check of a phase did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)
    print("  ok: %s" % msg, flush=True)


# ---------------------------------------------------------------------------
# The parent: phase runner (stdlib only)
# ---------------------------------------------------------------------------

def run_phase(name, argv, timeout):
    """Run one phase as a child in a process group of its own, pass its
    standard output through, and return its result dict. Whatever goes
    wrong comes back as ``{"ok": False, "error": ...}``; the group is
    killed before returning, so nothing the phase started survives."""
    print("=== phase %s (limit %.0f s)" % (name, timeout), flush=True)
    t0 = time.monotonic()
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    result = {}

    def pump():
        for line in proc.stdout:
            if line.startswith(RESULT):
                try:
                    result.update(json.loads(line[len(RESULT):]))
                except ValueError:
                    result["error"] = "unreadable result line"
            else:
                sys.stdout.write(line)
                sys.stdout.flush()

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        rc = None
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    reader.join(timeout=10)
    took = time.monotonic() - t0
    if rc is None:
        result = {"ok": False, "error": "timed out after %.0f s" % timeout}
    elif rc != 0:
        result = {"ok": False, "device": result.get("device"),
                  "error": result.get("error") or "exit code %d" % rc}
    elif "ok" not in result:
        result = {"ok": False, "error": "no result line"}
    print("=== phase %s %s in %.1f s (smoke)%s"
          % (name, "passed" if result["ok"] else "FAILED", took,
             "" if result["ok"] else ": %s" % result.get("error")),
          flush=True)
    return result


def run_phases(phases):
    """Run ``(name, argv, timeout)`` phases in turn until one fails.
    Returns ``(ok, device)``: ``device`` is the first full description a
    phase gave, and every phase must report the platform ``tpu``."""
    t0 = time.monotonic()
    device = None
    for name, argv, timeout in phases:
        left = DEADLINE_S - (time.monotonic() - t0)
        if left <= 1:
            print("=== phase %s FAILED: no time left" % name, flush=True)
            return False, device
        res = run_phase(name, argv, min(timeout, left))
        dev = res.get("device") or {}
        if device is None and dev.get("kind"):
            device = {k: dev.get(k) for k in ("platform", "kind", "count")}
        if not res["ok"]:
            return False, device
        if dev.get("platform") != "tpu":
            print("=== phase %s FAILED: ran on platform %r, not 'tpu'"
                  % (name, dev.get("platform")), flush=True)
            return False, device
    return device is not None, device


def finish(ok, device):
    """The contract's last line; returns the exit code."""
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip data-parallel phase "
                         "and its one-chip control")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return child_main(args.phase)

    def child(name, timeout):
        return (name, [sys.executable, "-u", os.path.abspath(__file__),
                       "--phase", name], timeout)

    if args.chips == 4:
        phases = [child("dp4", 1100)]
    else:
        phases = [child("device", 200), child("train", 500),
                  child("predict", 300), child("serve", 300),
                  child("lstm", 240)]
    return finish(*run_phases(phases))


# ---------------------------------------------------------------------------
# The children: one phase each. Bodies take (cfg, platform): the chip run
# passes FULL and "tpu"; tests/test_chip_smoke.py passes a tiny cfg and
# "cpu" to the same functions.
# ---------------------------------------------------------------------------

def child_main(phase):
    body, uses_jax = PHASES[phase]
    cache = None
    try:
        if uses_jax:
            cache = _CacheCounter()
        facts = body(FULL, "tpu")
        result = dict(facts, ok=True)
    except Exception as exc:   # the phase boundary: report, then fail
        traceback.print_exc()
        result = {"ok": False, "device": getattr(exc, "device", None),
                  "error": "%s: %s" % (type(exc).__name__, str(exc)[:300])}
    if cache is not None:
        print("  compile cache: %d hits, %d misses, dir %s"
              % (cache.hits, cache.misses, cache.dir), flush=True)
    print(RESULT + json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


class _CacheCounter:
    """Turns the persistent compile cache on (placed from outside, see
    `mxnet_tpu.compiled.enable_compile_cache`) and counts jax's own hit
    and miss events, so a second run shows the cache working."""

    def __init__(self):
        import jax
        from mxnet_tpu.compiled import enable_compile_cache
        self.dir = enable_compile_cache()
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _describe(platform):
    """This process's device description, as the contract's last line
    wants it; fails unless the default backend is ``platform``."""
    import jax
    devs = jax.devices()
    desc = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print("  device: %s" % json.dumps(desc), flush=True)
    if desc["platform"] != platform:
        failure = SmokeFailure("jax found platform %r, not %r"
                               % (desc["platform"], platform))
        failure.device = desc   # the last line still says what was found
        raise failure
    return desc


def _on_platform(arrays, platform, what):
    """Every jax array of ``arrays`` lives on ``platform`` devices."""
    found = {d.platform for a in arrays for d in a.devices()}
    check(found == {platform}, "%s on %s (found %s)"
          % (what, platform, sorted(found)))


def _counter(name):
    from mxnet_tpu import telemetry
    metric = telemetry.get_metric(name)
    return int(metric.value) if metric is not None else 0


def _close(got, want, what):
    """``got`` agrees with ``want`` within the MXU's fp32 tolerance
    (tests/test_tpu_smoke.py: f32 matmuls and convs run as bf16 passes by
    default, so 3e-2), taken relative to the scale of ``want``: through
    fifty layers the absolute size of a logit is arbitrary."""
    tol = 3e-2
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape and np.isfinite(got).all(),
          "%s: finite, shape %s" % (what, got.shape))
    scale = max(float(np.abs(want).max()), 1e-6)
    err = float(np.abs(got - want).max()) / scale
    check(err <= tol, "%s: max error %.2e of scale %.3g (bound %.0e)"
          % (what, err, scale, tol))


# -- phase 1 ----------------------------------------------------------------

def phase_device(cfg, platform):
    import jax
    import jax.numpy as jnp
    import jaxlib
    import numpy as np
    from jax import lax
    import mxnet_tpu as mx
    from mxnet_tpu import _native, xla_stats
    from mxnet_tpu.base import MXNetError

    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print("  jax %s, jaxlib %s, libtpu %s; compile cache dir %s"
          % (jax.__version__, jaxlib.__version__, libtpu,
             jax.config.jax_compilation_cache_dir), flush=True)
    desc = _describe(platform)
    dev = jax.devices()[0]

    # the context rule (Context.jax_device)
    check(mx.tpu(0).jax_device() == jax.local_devices()[0],
          "tpu(0) is local device 0")
    check(mx.cpu(0).jax_device().platform == "cpu",
          "cpu(0) resolves to a host device beside the %s" % platform)
    host = mx.nd.array(np.arange(3))
    check(host.context == mx.cpu(0), "an array with no ctx goes to cpu(0)")
    _on_platform([host._data], "cpu", "that array's buffer")
    if platform != "cpu":
        try:
            beyond = mx.tpu(len(jax.local_devices())).jax_device()
        except MXNetError as exc:
            check(True, "a device_id beyond the local count is refused (%s)"
                  % exc)
        else:
            raise SmokeFailure("tpu(%d) resolved to %s"
                               % (len(jax.local_devices()), beyond))
        peak = xla_stats.peak_flops_per_device()
        check(peak > 0, "peak table knows %r: %.0f TFLOP/s"
              % (dev.device_kind, peak / 1e12))

    # does block_until_ready block? a long program, then the three times
    n, iters = cfg["burn_n"], cfg["burn_iters"]
    x = jax.device_put(
        np.random.RandomState(SEED).rand(n, n).astype(np.float32),
        dev).astype(jnp.bfloat16)

    @jax.jit
    def burn(x):
        return lax.fori_loop(
            0, iters, lambda i, acc: jnp.tanh(acc @ x * 1e-3), x)

    # warm the program, and the small programs of the read below
    float(burn(x)[0, 0].astype(jnp.float32))
    t0 = time.perf_counter()
    out = burn(x)
    t1 = time.perf_counter()
    out.block_until_ready()
    t2 = time.perf_counter()
    float(out[0, 0].astype(jnp.float32))
    t3 = time.perf_counter()
    dispatch_s, block_s, read_s = t1 - t0, t2 - t1, t3 - t2
    print("  smoke: dispatch %.4f s, block_until_ready %.4f s, read after "
          "it %.4f s" % (dispatch_s, block_s, read_s), flush=True)
    blocks = block_s > 5 * dispatch_s and block_s > 5 * read_s
    if platform != "cpu":   # the CPU client may run the program inline
        check(blocks, "block_until_ready waits for the program")

    stats = dev.memory_stats() or {}
    if platform != "cpu":
        check(stats.get("bytes_in_use", 0) > 0
              and stats.get("bytes_limit", 0) > 0,
              "memory_stats() is real: %.1f MiB in use of %.1f GiB"
              % (stats.get("bytes_in_use", 0) / 2**20,
                 stats.get("bytes_limit", 0) / 2**30))

    t0 = time.perf_counter()
    native = _native.lib() is not None
    print("  native library: %s (%.1f s)"
          % ("built from src/ and loaded" if native
             else "not built, the Python paths are in use",
             time.perf_counter() - t0), flush=True)
    return {"device": desc}


# -- phase 2 and the four-chip phase ------------------------------------------

def _export_resnet(cfg, layout, prefix):
    """The zoo net, initialized from SEED on the host and traced to
    symbol JSON + params by `HybridBlock.export`. Returns (json, params)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    np.random.seed(SEED)
    mx.random.seed(SEED)
    kwargs = {} if layout == "NCHW" else {"layout": layout}
    net = vision.get_model(cfg["model"], classes=cfg["classes"], **kwargs)
    net.initialize(mx.init.Xavier())
    size = cfg["image"]
    shape = (1, 3, size, size) if layout == "NCHW" else (1, size, size, 3)
    net(mx.nd.zeros(shape))   # materialize the deferred shapes
    net.export(prefix)
    return "%s-symbol.json" % prefix, "%s-0000.params" % prefix


def _train_iter(cfg, batches, dtype="bfloat16"):
    """Synthetic NHWC images from SEED that a net can learn: ten label
    values, each a coarse 4x4 colour pattern under noise, so a few steps
    move the loss visibly (on pure noise SGD only raises it). The values
    are bf16's whatever ``dtype`` holds them."""
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    rng = np.random.default_rng(SEED)
    n, size = batches * cfg["train_batch"], cfg["image"]
    kinds = min(10, cfg["classes"])
    label = rng.integers(0, kinds, n)
    pattern = rng.random((kinds, 4, 4, 3), dtype=np.float32)
    pattern = np.repeat(np.repeat(pattern, size // 4, 1), size // 4, 2)
    data = 0.25 * rng.random((n, size, size, 3), dtype=np.float32)
    data += pattern[label]
    return mx.io.NDArrayIter(data.astype(jnp.bfloat16).astype(dtype),
                             label.astype(np.float32),
                             batch_size=cfg["train_batch"],
                             label_name="softmax_label")


def _resnet_module(cfg, contexts, sym_json, params_file, dtype="bfloat16"):
    """`Module` over the exported ResNet symbol + SoftmaxOutput, bound in
    ``dtype`` (params and data; BatchNorm statistics are f32 always). The
    params it returns are rounded to bf16, so that a float32 module
    starts from the very values a bfloat16 one does."""
    import mxnet_tpu as mx
    sym = mx.sym.SoftmaxOutput(mx.sym.load(sym_json), name="softmax")
    mod = mx.mod.Module(sym, context=contexts)
    size, batch = cfg["image"], cfg["train_batch"]
    type_dict = {"data": dtype}
    type_dict.update({p: dtype for p in mod._param_names})
    mod.bind(data_shapes=[("data", (batch, size, size, 3))],
             label_shapes=[("softmax_label", (batch,))],
             type_dict=type_dict)
    params = {k.split(":", 1)[1]: v.astype("bfloat16")
              for k, v in mx.nd.load(params_file).items()}
    return mod, params


class _Watch:
    """batch_end_callback: per-batch loss, arrival time and the compile
    counter, read off the metric `fit` updates."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.times, self.losses, self.compiles = [], [], []

    def __call__(self, param):
        self.times.append(time.perf_counter() - self.t0)
        self.losses.append(float(param.eval_metric.get()[1]))
        self.compiles.append(_counter("jit_compiles_total"))
        param.eval_metric.reset()


def _fit(mod, it, params, **kwargs):
    import mxnet_tpu as mx
    watch = _Watch()
    it.reset()
    mod.fit(it, eval_metric="ce", optimizer="sgd",
            optimizer_params={"learning_rate": 0.002, "momentum": 0.9},
            initializer=mx.init.Xavier(), arg_params=params,
            allow_missing=True, batch_end_callback=watch, **kwargs)
    return watch


def _check_not_rising(watch, what):
    """Over a few tens of steps; the first handful of a young net, each
    on a batch it has not seen, go either way."""
    import numpy as np
    losses = np.asarray(watch.losses)
    head, tail = losses[:3].mean(), losses[-3:].mean()
    check(tail <= head * 1.02, "%s: loss not rising (%.3f -> %.3f)"
          % (what, head, tail))


def _check_steps(watch, what, per_dispatch=1):
    import numpy as np
    losses = np.asarray(watch.losses)
    check(np.isfinite(losses).all(), "%s: %d losses finite"
          % (what, len(losses)))
    check(watch.compiles[-1] == watch.compiles[per_dispatch - 1],
          "%s: no compile after the first dispatch (%d programs)"
          % (what, watch.compiles[-1]))
    # one arrival per dispatch: with K batches in a dispatch the K
    # callbacks come in a burst
    arrivals = watch.times[per_dispatch - 1::per_dispatch]
    steady = np.diff(arrivals) / per_dispatch
    print("  smoke: %s: first step after %.1f s (compile included), "
          "steady %.1f ms/step (median of %d dispatches)"
          % (what, arrivals[0],
             1e3 * float(np.median(steady)) if len(steady) else float("nan"),
             len(steady)), flush=True)


def _module_arrays(mod):
    return [mod._exec.arg_dict[n]._data for n in mod._param_names]


def _step_text(mod):
    """Text of the executables `mod`'s fused step has compiled so far: it
    shows which kernels and collectives the step holds. `Module` and
    `CompiledProgram` offer no accessor for it and this script is no
    reason to add one, so it reads their private fields here, in this
    one place."""
    step_fn = mod._fused_plan[3]
    return "\n".join(entry.compiled.as_text()
                     for entry in step_fn._cache.values()
                     if entry.compiled is not None)


def phase_train(cfg, platform):
    import jax
    import mxnet_tpu as mx
    desc = _describe(platform)
    os.makedirs(WORK, exist_ok=True)
    sym_json, params_file = _export_resnet(
        cfg, "NHWC", os.path.join(WORK, "train"))
    mod, params = _resnet_module(cfg, mx.tpu(), sym_json, params_file)
    it = _train_iter(cfg, cfg["train_batches"])

    plain = _fit(mod, it, params, num_epoch=1)
    _check_steps(plain, "fused _step")
    _check_not_rising(plain, "fused _step")
    _on_platform(_module_arrays(mod), platform, "parameters after _step")

    mod.scan_donate_params = True
    k = cfg["scan_k"]
    scan = _fit(mod, it, params, num_epoch=cfg["scan_epochs"],
                batches_per_dispatch=k, scan_unroll=cfg["scan_unroll"])
    steps = cfg["scan_epochs"] * cfg["train_batches"]
    check(len(scan.losses) == steps and steps % k == 0,
          "_step_scan ran %d steps, %d to a dispatch" % (steps, k))
    _check_steps(scan, "_step_scan (donated params)", per_dispatch=k)
    _check_not_rising(scan, "_step_scan (donated params)")
    _on_platform(_module_arrays(mod), platform,
                 "parameters after _step_scan")
    check(scan.losses[-1] < plain.losses[0],
          "loss fell over the run (%.3f -> %.3f)"
          % (plain.losses[0], scan.losses[-1]))

    check(_counter("jit_aot_fallbacks_total") == 0,
          "jit_aot_fallbacks_total is 0")
    if platform != "cpu":
        stats = jax.devices()[0].memory_stats()
        held = sum(a.nbytes for a in _module_arrays(mod))
        check(stats["bytes_in_use"] >= held,
              "memory_stats(): %.0f MiB in use, parameters are %.0f MiB"
              % (stats["bytes_in_use"] / 2**20, held / 2**20))
    return {"device": desc}


def _rel(got, want):
    """``|got - want|`` over ``|want|``, both Euclidean over the whole
    array: one flipped ReLU moves a single element a long way and the
    norm very little."""
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def _worst(got, want):
    """The largest `_rel` over two dicts of arrays, and where it is."""
    return max((_rel(got[name], want[name]), name) for name in want)


def phase_dp4(cfg, platform):
    """`Module(context=[tpu(0..3)]).fit` against the same steps from the
    same seed on one chip, in float32 and in bfloat16: four runs from
    identical parameters (rounded to bf16) on identical data.

    The data-parallel policy is GSPMD over one program (batch sharded on
    the mesh's `data` axis, params replicated), so BatchNorm takes its
    statistics over the GLOBAL batch, as on one chip, and the gradient
    all-reduce sums the same terms; per-shard statistics would give
    another loss and other gradients from the first step on. Between
    four chips and one only the order of the reductions differs, so the
    tolerances follow from the arithmetic, not from the mesh:

    float32 (matmul precision `highest`) proves the structure. Every
    parameter's gradient of the first step, and every weight after that
    step's update, is held to the one-chip run: within 1e-3 of its norm
    at the last layer and 5e-2 everywhere (on the chip 4e-6 and 2.4e-2
    at worst, median 1.7e-2; PR 21), where a term left out of a
    reduction or a statistic taken per shard costs 5e-1 and more. Not
    to f32's 1e-7: BatchNorm's one-pass variance (`ops/nn.py`
    `_bn_stats`, E[x^2] - E[x]^2 in f32) turns the reduction order into
    1e-5 of the activations, and the layers above and the odd flipped
    ReLU carry that to about 1e-2 of a gradient's norm (CPU rehearsal,
    ResNet-18: the same in float64, and 3e-4 with the two-pass
    variance, so it is this and not the mesh).

    bfloat16, the configuration users train, cannot be held to its own
    one-chip run that way: on ONE chip its first gradients below the
    last layer lie as far from the float32 reference as their own norm
    (median 1.22 of it on the chip, 0.35 in the rehearsal). BatchNorm's
    backward subtracts the batch mean of dy, which at initialization is
    most of dy, and what is left carries the bf16 rounding of what was
    taken away, fifty layers deep. Four chips round differently, not
    worse. So each bf16 run is measured against the float32 one-chip
    reference, and four chips are held to lie no further from it than
    one chip does (twice its distance plus 5e-2). Where bf16's
    arithmetic is short it is held directly: the first step's loss
    within 1e-2 of one chip's, the last layer's gradients within 5e-2
    (the partial sums are rounded to bf16 before the all-reduce).

    The steps after the first are not compared between runs, only
    printed. They start from parameters that differ by the above, each
    on a batch the net has not seen, and at this learning rate a young
    net's losses jump by whole units a step: on the chip the two
    FLOAT32 runs, 2.4e-2 apart in their first gradients, were 5.4
    against 2.3 in loss three steps later. (The update rule over many
    steps under the mesh is held tightly where it can be, on a
    well-conditioned net: tests/test_module_dp.py.) What is exact is
    checked exactly: after the steps the four replicas of every
    parameter are equal bit for bit."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    desc = _describe(platform)
    n = 4
    check(len(jax.local_devices()) >= n, "%d local devices" % n)
    os.makedirs(WORK, exist_ok=True)
    sym_json, params_file = _export_resnet(
        cfg, "NHWC", os.path.join(WORK, "dp"))
    one, four = [mx.tpu(0)], [mx.tpu(i) for i in range(n)]

    def read(mod, arrays):
        return {name: arrays[name].asnumpy() for name in mod._param_names}

    def run(contexts, dtype):
        what = "%s on %d chip(s)" % (dtype, len(contexts))
        mod, params = _resnet_module(cfg, contexts, sym_json, params_file,
                                     dtype)
        first = _fit(mod, _train_iter(cfg, 1, dtype), params, num_epoch=1)
        out = {"mod": mod, "loss": first.losses[0],
               "grads": read(mod, mod._exec.grad_dict),
               "weights": read(mod, mod._exec.arg_dict)}
        rest = _fit(mod, _train_iter(cfg, cfg["dp_steps"], dtype), params,
                    num_epoch=1)
        _check_steps(rest, what)
        out["losses"] = rest.losses
        out["weights_end"] = read(mod, mod._exec.arg_dict)
        return out

    with jax.default_matmul_precision("highest"):
        ref = run(one, "float32")
        del ref["mod"]            # one chip has to hold the next run too
        f32 = run(four, "float32")
        del f32["mod"]
    bf1 = run(one, "bfloat16")
    del bf1["mod"]
    bf4 = run(four, "bfloat16")
    dp_mod = bf4["mod"]
    last = dp_mod._param_names[-2:]     # the last layer's weight and bias

    # the numbers first: every comparison is made and printed, and the
    # phase fails at its end if one did not hold (a four-chip run is
    # dear; it says all it has to say)
    failed = []

    def hold(err, bound, msg):
        ok = err <= bound
        print("  %s: %s: %.2e (bound %.0e)"
              % ("ok" if ok else "NOT HELD", msg, err, bound), flush=True)
        if not ok:
            failed.append(msg)

    def scale_err(got, want):   # as `_close`: largest error over the scale
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        return float(np.abs(got - want).max() / np.abs(want).max())

    against = "%d chips against one" % n
    hold(scale_err(f32["loss"], ref["loss"]), 1e-4,
         "float32: first step's loss, " + against)
    for what, label in (("grads", "first step's gradients"),
                        ("weights", "weights after the first step")):
        err, name = _worst(f32[what], ref[what])
        hold(err, 5e-2, "float32: %s, %s: worst of %d, of its norm (%s; "
             "median %.2e)" % (label, against, len(ref[what]), name,
                               float(np.median([_rel(f32[what][k], v) for
                                                k, v in ref[what].items()]))))
    for name in last:
        hold(_rel(f32["grads"][name], ref["grads"][name]), 1e-3,
             "float32: first step's gradient of %s, %s, of its norm"
             % (name, against))

    hold(scale_err(bf4["loss"], bf1["loss"]), 1e-2,
         "bfloat16: first step's loss, " + against)
    for name in last:
        hold(_rel(bf4["grads"][name], bf1["grads"][name]), 5e-2,
             "bfloat16: first step's gradient of %s, %s, of its norm"
             % (name, against))
    for what, label in (("grads", "first step's gradients"),
                        ("weights", "weights after the first step")):
        far = []
        for name in ref[what]:
            e1 = _rel(bf1[what][name], ref[what][name])
            e4 = _rel(bf4[what][name], ref[what][name])
            far.append((e4 - 2 * e1, e1, e4, name))
        over, e1, e4, name = max(far)
        hold(over, 5e-2, "bfloat16: %s, distance from the float32 "
             "reference, %d chips less twice one chip: worst of %d (%s: "
             "one chip %.2e, %d chips %.2e; medians %.2e and %.2e)"
             % (label, n, len(far), name, e1, n, e4,
                float(np.median([f[1] for f in far])),
                float(np.median([f[2] for f in far]))))
        err, name = _worst(bf4[what], bf1[what])
        print("  info: bfloat16: %s, %s: worst is %.2e of its norm (%s)"
              % (label, against, err, name), flush=True)
    name = dp_mod._param_names[0]       # the first convolution
    print("  info: first step's gradient of %s, of its norm: float32 %s "
          "%.2e; bfloat16 from the float32 reference, one chip %.2e, %d "
          "chips %.2e; bfloat16 %s %.2e"
          % (name, against, _rel(f32["grads"][name], ref["grads"][name]),
             _rel(bf1["grads"][name], ref["grads"][name]), n,
             _rel(bf4["grads"][name], ref["grads"][name]), against,
             _rel(bf4["grads"][name], bf1["grads"][name])), flush=True)
    for what, a, b in (("float32", f32, ref), ("bfloat16", bf4, bf1)):
        err, name = _worst(a["weights_end"], b["weights_end"])
        print("  info: %s: weights after %d more steps, %s: worst is %.2e "
              "of its norm (%s); losses one chip %s, %d chips %s"
              % (what, cfg["dp_steps"], against, err, name,
                 " ".join("%.3f" % x for x in b["losses"]), n,
                 " ".join("%.3f" % x for x in a["losses"])), flush=True)
    # structure, on the configuration users train
    data = dp_mod._exec.arg_dict["data"]._data
    shard_devs = {s.device for s in data.addressable_shards}
    check(len(shard_devs) == n and all(
        s.data.shape[0] == cfg["train_batch"] // n
        for s in data.addressable_shards),
        "the batch lies in %d shards of %d on %d distinct devices"
        % (len(data.addressable_shards), cfg["train_batch"] // n,
           len(shard_devs)))
    _on_platform([data] + _module_arrays(dp_mod), platform,
                 "batch and parameters")
    check(all(len(a.devices()) == n for a in _module_arrays(dp_mod)),
          "every parameter is present on all %d devices" % n)
    check("all-reduce" in _step_text(dp_mod),
          "the compiled step has an all-reduce")
    if platform != "cpu":
        in_use = [d.memory_stats()["bytes_in_use"]
                  for d in jax.local_devices()[:n]]
        check(all(b > 0 for b in in_use), "bytes_in_use on every chip: %s"
              % ", ".join("%.0f MiB" % (b / 2**20) for b in in_use))
    for name in dp_mod._param_names:
        replicas = [np.asarray(s.data) for s in
                    dp_mod._exec.arg_dict[name]._data.addressable_shards]
        if not (len(replicas) == n and all(
                np.array_equal(r, replicas[0]) for r in replicas[1:])):
            raise SmokeFailure("the %d replicas of %s differ" % (n, name))
    check(True, "the %d replicas of each of %d parameters are equal bit "
          "for bit after the steps" % (n, len(dp_mod._param_names)))

    if failed:
        raise SmokeFailure("%d comparison(s) not held: %s"
                           % (len(failed), "; ".join(failed))[:600])
    return {"device": desc}


# -- phase 3 ----------------------------------------------------------------

def phase_predict(cfg, platform):
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    desc = _describe(platform)
    os.makedirs(WORK, exist_ok=True)
    ctx = mx.tpu()
    np.random.seed(SEED)
    mx.random.seed(SEED)
    net = vision.get_model(cfg["model"], classes=cfg["classes"])
    net.initialize(mx.init.Xavier(), ctx=ctx)
    batch, size = cfg["predict_batch"], cfg["image"]
    x_np = np.random.RandomState(SEED).rand(batch, 3, size, size) \
        .astype(np.float32)
    x = mx.nd.array(x_np, ctx=ctx)

    eager = net(x)
    _on_platform([p.data()._data for p in net.collect_params().values()]
                 + [eager._data], platform, "parameters and eager output")
    eager = eager.asnumpy()
    net.hybridize()
    t0 = time.perf_counter()
    hybrid = net(x)
    hybrid.wait_to_read()
    print("  smoke: hybridized forward, first call %.1f s (compile "
          "included)" % (time.perf_counter() - t0), flush=True)
    _on_platform([hybrid._data], platform, "hybridized output")
    compiles = _counter("jit_compiles_total")
    hybrid = net(x).asnumpy()
    check(_counter("jit_compiles_total") == compiles,
          "no compile on the second hybridized call")
    check(hybrid.shape == (batch, cfg["classes"]), "output shape %s"
          % (hybrid.shape,))
    _close(hybrid, eager, "hybridized against eager on the %s" % platform)
    if platform == "tpu":
        import jax
        weights = tuple(net.collect_params()[n].data()._data
                        for n in net._param_order)
        text = net._cached_jit.lower(weights, jax.random.PRNGKey(SEED),
                                     False, ctx, x._data).as_text()
        check("x12x4x4xf32>" in text, "the stem is lowered as space-to-"
              "depth: a 4x4 convolution over 12 channels")

    # the same net on the host: the reference the chip is held to
    prefix = os.path.join(WORK, "predict")
    net.save_parameters(prefix + ".gluon.params")
    host_net = vision.get_model(cfg["model"], classes=cfg["classes"])
    host_net.load_parameters(prefix + ".gluon.params", ctx=mx.cpu())
    host = host_net(mx.nd.array(x_np, ctx=mx.cpu()))
    _on_platform([host._data], "cpu", "the host reference")
    _close(hybrid, host.asnumpy(), "hybridized against the net on mx.cpu()")
    check(_counter("jit_aot_fallbacks_total") == 0,
          "jit_aot_fallbacks_total is 0")

    # for phase 4: what the server loads, and what it must answer
    net.export(prefix)
    np.save(prefix + ".x.npy", x_np)
    np.save(prefix + ".y.npy", hybrid)
    return {"device": desc}


# -- phase 4 ----------------------------------------------------------------

def _http(port, method, path, doc=None, timeout=120):
    import urllib.request
    body = None if doc is None else json.dumps(doc).encode()
    req = urllib.request.Request(
        "http://127.0.0.1:%d%s" % (port, path), data=body, method=method,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read()


def phase_serve(cfg, platform):
    """No jax in this process: the server it starts needs the chip."""
    import numpy as np
    prefix = os.path.join(WORK, "predict")
    x = np.load(prefix + ".x.npy")
    y = np.load(prefix + ".y.npy")
    size = cfg["image"]
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [REPO] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "mxnet_tpu.serving.server",
         "--symbol", prefix + "-symbol.json",
         "--params", prefix + "-0000.params",
         "--input", "data:3,%d,%d" % (size, size), "--port", "0",
         "--max-batch", str(cfg["serve_max_batch"]), "--allow-shutdown"],
        stdout=subprocess.PIPE, text=True, env=env, cwd=REPO)
    try:
        line = "x"
        while line and not line.startswith("SERVING "):
            line = proc.stdout.readline()
        check(line.startswith("SERVING "), "the server printed its "
              "SERVING line")
        info = json.loads(line[len("SERVING "):])
        print("  smoke: serving after %.1f s (%d warm-up compiles, "
              "buckets %s)" % (time.perf_counter() - t0,
                               info["warmup_compiles"], info["buckets"]),
              flush=True)
        port = info["port"]
        lo = 0
        for n in cfg["serve_sizes"]:
            code, raw = _http(port, "POST", "/predict",
                              {"inputs": {"data": x[lo:lo + n].tolist()}})
            check(code == 200, "POST /predict of %d example(s): 200" % n)
            _close(json.loads(raw)["outputs"][0], y[lo:lo + n],
                   "served answer for examples %d..%d against phase 3"
                   % (lo, lo + n - 1))
            lo += n
        code, raw = _http(port, "GET", "/healthz")
        health = json.loads(raw)
        check(code == 200 and health["cold_compiles"] == 0,
              "/healthz: cold_compiles 0")
        check(health["platform"] == platform,
              "/healthz: the replicas' buffers are on %r"
              % health["platform"])
        code, raw = _http(port, "GET", "/metrics")
        want = 'serving_requests_total{status="ok"} %d' \
            % len(cfg["serve_sizes"])
        metrics = raw.decode()
        check(want in metrics, "/metrics: %s" % want)
        fell_back = [ln for ln in metrics.splitlines()
                     if ln.startswith("jit_aot_fallbacks_total")
                     and float(ln.rsplit(" ", 1)[1]) > 0]
        check(not fell_back, "/metrics: jit_aot_fallbacks_total is 0")
        check(_http(port, "POST", "/shutdown")[0] == 200,
              "POST /shutdown: 200")
        proc.communicate(timeout=60)
        check(proc.returncode == 0, "the server exited with code 0")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    return {"device": {"platform": health["platform"]}}


# -- phase 5 ----------------------------------------------------------------

def phase_lstm(cfg, platform):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.ops.pallas_kernels import fused_lstm, _lstm_scan_ref
    desc = _describe(platform)
    c = cfg["lstm"]
    T, B, V, H = c["bptt"], c["batch"], c["vocab"], c["hidden"]

    # examples/rnn/word_lm/benchmark.py's model, fed by an NDArrayIter
    data = mx.sym.Variable("data")                      # (T, B) token ids
    emb = mx.sym.Embedding(data, input_dim=V, output_dim=H, name="embed")
    rnn = mx.sym.RNN(emb, state_size=H, num_layers=c["layers"],
                     mode="lstm", name="lstm")           # (T, B, H)
    dec = mx.sym.FullyConnected(mx.sym.Reshape(rnn, shape=(-1, H)),
                                num_hidden=V, name="decoder")
    label = mx.sym.Reshape(mx.sym.Variable("softmax_label"), shape=(-1,))
    net = mx.sym.SoftmaxOutput(dec, label, name="softmax")
    rng = np.random.RandomState(SEED)
    n = c["batches"]
    it = mx.io.NDArrayIter(
        rng.randint(0, V, (n * T, B)).astype(np.float32),
        rng.randint(0, V, (n * T, B)).astype(np.float32),
        batch_size=T, label_name="softmax_label")
    mod = mx.mod.Module(net, context=mx.tpu())
    watch = _Watch()
    mod.fit(it, num_epoch=1, eval_metric="ce", optimizer="sgd",
            optimizer_params={"learning_rate": 1.0, "clip_gradient": 0.25},
            initializer=mx.init.Xavier(), batch_end_callback=watch)
    check(np.isfinite(watch.losses).all() and len(watch.losses) == n,
          "%d train steps, losses finite (%.3f -> %.3f)"
          % (n, watch.losses[0], watch.losses[-1]))
    check(watch.compiles[-1] == watch.compiles[0],
          "no compile after the first step")
    _on_platform(_module_arrays(mod), platform, "parameters")
    check(_counter("jit_aot_fallbacks_total") == 0,
          "jit_aot_fallbacks_total is 0")
    print("  smoke: first step after %.1f s (compile included), steady "
          "%.1f ms/step" % (watch.times[0], 1e3 * float(
              np.median(np.diff(watch.times)))), flush=True)
    if platform == "tpu":
        # forward and backward kernel for each of the layers, and not
        # the lax.scan fallback of ops/nn.py
        calls = _step_text(mod).count("tpu_custom_call")
        check(calls >= 2 * c["layers"],
              "the compiled step has %d tpu_custom_call (Pallas LSTM "
              "forward and backward, %d layers)" % (calls, c["layers"]))

    # the kernels' gradients against the lax.scan reference, same inputs
    dev = jax.devices()[0]
    shapes = [(T, B, H), (B, H), (B, H), (H, 4 * H), (H, 4 * H), (4 * H,)]
    args = [jax.device_put((rng.randn(*s) * 0.1).astype(np.float32), dev)
            for s in shapes]
    proj = jax.device_put(rng.randn(T, B, H).astype(np.float32), dev)

    def loss(lstm):
        def f(*a):
            hseq, hn, cn = lstm(*a)
            return (hseq * proj).sum() + hn.sum() + cn.sum()
        return jax.jit(jax.grad(f, argnums=tuple(range(6))))

    got = loss(fused_lstm)(*args)
    with jax.default_matmul_precision("highest"):   # the reference in f32
        want = loss(_lstm_scan_ref)(*args)
    _on_platform(got, platform, "kernel gradients")
    for name, g, w in zip(("x", "h0", "c0", "wx", "wh", "b"), got, want):
        _close(g, w, "d/d%s of fused_lstm against _lstm_scan_ref" % name)
    return {"device": desc}


PHASES = {   # name -> (body, whether the child may import jax)
    "device": (phase_device, True),
    "train": (phase_train, True),
    "predict": (phase_predict, True),
    "serve": (phase_serve, False),
    "lstm": (phase_lstm, True),
    "dp4": (phase_dp4, True),
}


if __name__ == "__main__":
    sys.exit(main())
