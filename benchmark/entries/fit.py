"""Entry: `Module.fit`, as a user calls it.

One `Module` is bound, given the seeded parameters, and driven by a first
`fit` over a few batches (the warm-up: it compiles or reads from the cache
every program the window uses, and its first steps are what `correct`
compares). The SAME module then runs a second `fit` of one epoch, the
window: the iterator ends the epoch once ``--seconds`` have passed, and the
clock stops when the parameters are ready after `fit` has returned.

Mix parameters (``benchmark/traffic/<mix>.json``):
  batches_per_dispatch  K of `fit(batches_per_dispatch=K)`; 1 is the plain
                        `_step`, above 1 the `_step_scan` path
  pool_batches          distinct seeded batches held in host memory, cycled
                        in an order drawn from the seed
  metric_read_every     the callback reads the metric every so many
                        batches, as `mx.callback.Speedometer` does
  warmup_batches        batches of the first `fit`
  trace_batches         batches of the traced stretch (``--trace 1``)
"""
import gc
import importlib
import logging
import time
import warnings

import jax
import mxnet_tpu as mx
import numpy as np

from benchmark import reference

FOLLOW = 3   # steps of the program that the reference follows, at least


class PoolIter(mx.io.DataIter):
    """A `DataIter` over a pool of ready `DataBatch`es in host memory. It
    yields ``limit`` batches, or, with ``seconds``, ends the epoch at the
    first dispatch boundary after that long since its first batch."""

    def __init__(self, batches, order, descs, group, limit=None,
                 seconds=None):
        super().__init__(descs[0][0].shape[0])
        self.batches, self.order, self.group = batches, order, group
        self.provide_data, self.provide_label = descs
        self.limit, self.seconds = limit, seconds
        self.n, self.t_open = 0, None

    def __next__(self):
        now = time.perf_counter()
        if self.t_open is None:
            self.t_open = now
        if self.n % self.group == 0 and (
                (self.limit is not None and self.n >= self.limit) or
                (self.seconds is not None
                 and now - self.t_open >= self.seconds)):
            raise StopIteration
        batch = self.batches[self.order[self.n % len(self.order)]]
        self.n += 1
        return batch

    next = __next__


class Watch:
    """batch_end_callback of the window: notes the clock, and every
    ``read_every`` batches reads and resets the metric. With ``tracer`` it
    starts and stops the profiler around the traced stretch."""

    def __init__(self, read_every, tracer=None):
        self.read_every, self.tracer = read_every, tracer
        self.times, self.reads = [], []

    def __call__(self, param):
        self.times.append(time.perf_counter())
        if len(self.times) % self.read_every == 0:
            self.reads.append(float(param.eval_metric.get()[1]))
            param.eval_metric.reset()
        if self.tracer is not None:
            self.tracer.step(len(self.times))


class Tracer:
    """Profiles ``batches`` steps from the first dispatch boundary after
    ``after_s`` seconds of the window."""

    def __init__(self, trace_dir, t_open, after_s, batches, group):
        self.dir, self.t_open, self.after_s = trace_dir, t_open, after_s
        self.batches, self.group = batches, group
        self.started_at = self.stopped_at = None

    def step(self, n):
        if self.stopped_at is not None or n % self.group:
            return
        if self.started_at is None:
            if time.perf_counter() - self.t_open() >= self.after_s:
                # device events only: see the head of `reduce.py`
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 0
                options.enable_hlo_proto = False
                jax.profiler.start_trace(self.dir, profiler_options=options)
                self.started_at = n
        elif n - self.started_at >= self.batches + self.group:
            jax.profiler.stop_trace()
            self.stopped_at = n

    def close(self):
        if self.started_at is not None and self.stopped_at is None:
            jax.profiler.stop_trace()
            self.stopped_at = -1


class FirstSteps:
    """batch_end_callback of the warm-up: every step's loss, the optimizer's
    state after the first step and the parameters after ``follow`` steps,
    copied to host memory as they pass."""

    def __init__(self, mod, follow, per_dispatch):
        self.mod, self.follow, self.per_dispatch = mod, follow, per_dispatch
        self.losses, self.first_mom, self.params = [], None, None
        self.first_probs = None

    def __call__(self, param):
        self.losses.append(float(param.eval_metric.get()[1]))
        param.eval_metric.reset()
        n = len(self.losses)
        if n == 1:
            # `fit` hands its callbacks its own locals: in a dispatch of
            # several batches ``outs`` holds this batch's outputs
            outs = param.locals.get("outs") if self.per_dispatch > 1 \
                else dict(zip(self.mod.output_names, self.mod.get_outputs()))
            self.first_probs = np.asarray(
                outs[self.mod.output_names[0]]._data).astype(np.float32)
            if self.per_dispatch == 1:
                self.first_mom = _optimizer_state(self.mod)
        if n == self.follow:
            args, _ = self.mod.get_params()
            self.params = {k: np.asarray(v._data) for k, v in args.items()}


def _optimizer_state(mod):
    """{parameter: momentum} on the host. `Module` offers the optimizer's
    state only as a file (`save_optimizer_states`); the benchmark reads the
    updater's dict here, in this one place (PERF.md, Open questions)."""
    out = {}
    for i, name in enumerate(mod._param_names):
        state = mod._updater.states.get(i)
        if state is not None:
            out[name] = np.asarray(state._data)
    return out


def counters():
    from mxnet_tpu import telemetry
    out = {}
    for name in ("jit_compiles_total", "jit_cache_hits_total",
                 "jit_aot_fallbacks_total"):
        metric = telemetry.get_metric(name)
        out[name] = int(metric.value) if metric is not None else 0
    return out


class Job:
    """The cell, built from the seed: module, parameters, pool."""

    def __init__(self, run):
        import jax.numpy as jnp
        self.run = run
        cfg, mix = run.config, run.traffic
        self.k = int(mix["batches_per_dispatch"])
        # whole dispatches: the state can be read only between them
        self.follow = -(-FOLLOW // self.k) * self.k
        self.optimizer = cfg["optimizer"]
        self.layout, self.dtype = cfg["layout"], cfg["dtype"]
        chips = run.cell["chips"]
        self.batch = cfg["batch"] * chips
        size = cfg["image"]
        self.shape = (self.batch, size, size, 3) if self.layout == "NHWC" \
            else (self.batch, 3, size, size)
        self.layers = importlib.import_module(
            "benchmark.refs." + cfg["reference"]).layers(
                cfg["reference_args"])
        self.shapes = reference.param_shapes(self.layers, self.shape,
                                             self.layout)

        sym = run.load("models", cfg["builder"]).symbol(cfg)
        grown = [n for n in sym.list_arguments()
                 if n not in ("data", "softmax_label")]
        if sorted(grown) != sorted(self.shapes):
            raise SystemExit(
                "the program's symbol and the plain reference name different "
                "parameters: %s" % sorted(set(grown) ^ set(self.shapes))[:6])
        contexts = [mx.tpu(i) for i in range(chips)]
        self.mod = mx.mod.Module(sym, context=contexts)
        types = {n: self.dtype for n in self.shapes}
        types["data"] = self.dtype
        self.mod.bind(data_shapes=[("data", self.shape)],
                      label_shapes=[("softmax_label", (self.batch,))],
                      type_dict=types)
        arg_shapes, _, _ = sym.infer_shape(
            data=self.shape, softmax_label=(self.batch,))
        for name, have in zip(sym.list_arguments(), arg_shapes):
            if name in self.shapes and tuple(have) != self.shapes[name]:
                raise SystemExit("%s: the program has %s, the reference %s"
                                 % (name, have, self.shapes[name]))

        device = jax.devices()[0]
        params = reference.make_params(self.shapes, run.seed,
                                       jnp.dtype(self.dtype), cfg["init"])
        self.arg_params = {n: mx.nd.NDArray(v, contexts[0])
                           for n, v in params.items()}
        images, labels = reference.make_pool(
            run.seed, int(mix["pool_batches"]), self.batch, size,
            cfg["classes"], self.layout, jnp.dtype(self.dtype))
        images, labels = np.asarray(images), np.asarray(labels)
        del params
        self.pool = (images, labels)
        self.batches = [
            mx.io.DataBatch(data=[mx.nd.array(x, ctx=mx.cpu())],
                            label=[mx.nd.array(y, ctx=mx.cpu())])
            for x, y in zip(images, labels)]
        order = np.random.default_rng(run.seed).permutation(len(images))
        self.order = [int(i) for i in order]
        self.descs = (
            [mx.io.DataDesc("data", self.shape, np.dtype(images.dtype),
                            self.layout)],
            [mx.io.DataDesc("softmax_label", (self.batch,), np.float32,
                            "N")])
        run.log("built: %d parameters in %d leaves on %s, pool of %d batches "
                "of %d rows (%.0f MB each) in host memory, order %s"
                % (sum(int(np.prod(s)) for s in self.shapes.values()),
                   len(self.shapes), device, len(images), self.batch,
                   images[0].nbytes / 1e6, self.order))

    def fit(self, it, callback):
        opt = self.optimizer
        self.mod.fit(
            it, eval_metric="ce", optimizer=opt["name"],
            optimizer_params={"learning_rate": opt["learning_rate"],
                              "momentum": opt["momentum"], "wd": opt["wd"]},
            initializer=mx.init.Xavier(), arg_params=self.arg_params,
            allow_missing=True, num_epoch=1, batch_end_callback=callback,
            batches_per_dispatch=self.k)
        self.arg_params = None   # the module owns them from here on

    def iterator(self, **kwargs):
        return PoolIter(self.batches, self.order, self.descs,
                        self.k, **kwargs)

    def ready(self):
        """Every dispatched program has finished (MXNet's own fence)."""
        mx.nd.waitall()

    def first_steps(self):
        """The warm-up `fit`; returns what `compare.numbers` takes as the
        program's side."""
        warm = int(self.run.traffic["warmup_batches"])
        if warm < self.follow or warm % self.k:
            raise SystemExit("warmup_batches must hold %d steps in whole "
                             "dispatches of %d" % (self.follow, self.k))
        first = FirstSteps(self.mod, self.follow, self.k)
        self.fit(self.iterator(limit=warm), first)
        self.ready()
        return {"losses": first.losses[:self.follow],
                "first_mom": first.first_mom, "params": first.params,
                "first_probs": first.first_probs}

    def first_batches(self):
        """The batches the first steps saw, as host arrays."""
        images, labels = self.pool
        return [(images[i], labels[i])
                for i in (self.order * self.follow)[:self.follow]]

    def reference_side(self):
        """A function that runs the plain reference over the same first
        batches from the same seeded parameters, in the configuration's
        precision or the ``policy`` it is given, to be called once the
        program's state is freed."""
        import jax.numpy as jnp
        layers, layout, opt = self.layers, self.layout, self.optimizer
        shapes, seed, init = self.shapes, self.run.seed, self.run.config["init"]
        dtype = jnp.dtype(self.dtype)
        batches = self.first_batches()

        def side(policy=reference.POLICY[self.dtype]):
            w0 = reference.make_params(shapes, seed, dtype, init)
            out = reference.follow(
                layers, layout, policy, opt, w0,
                [(jnp.asarray(x), jnp.asarray(y)) for x, y in batches])
            host = {"losses": out["losses"],
                    "first_probs": np.asarray(out["first_probs"]),
                    "first_mom": {n: np.asarray(v)
                                  for n, v in out["first_mom"].items()},
                    "params": {n: np.asarray(v)
                               for n, v in out["params"].items()}}
            return host, {n: np.asarray(v) for n, v in w0.items()}

        return side


def quiet():
    """`fit` called twice warns that it is bound and initialized already,
    and logs each epoch; neither belongs among the run's lines."""
    warnings.filterwarnings("ignore", message=".*already.*")
    warnings.filterwarnings("ignore", message=".*Already.*")
    logging.getLogger().setLevel(logging.ERROR)


def run(run):
    quiet()
    t0 = time.perf_counter()
    job = Job(run)
    t1 = time.perf_counter()
    program = job.first_steps()
    run.log("warm-up: %d batches through fit, losses of the first %d steps %s"
            % (int(run.traffic["warmup_batches"]), job.follow,
               ["%.4f" % v for v in program["losses"]]))
    run.log("set-up: model, weights, pool and bind %.1f s, warm-up fit %.1f s"
            % (t1 - t0, time.perf_counter() - t1))

    it = job.iterator(seconds=run.seconds)
    tracer = None
    if run.trace:
        tracer = Tracer(run.trace_dir, lambda: it.t_open, 0.4 * run.seconds,
                        int(run.traffic["trace_batches"]), job.k)
    watch = Watch(int(run.traffic["metric_read_every"]), tracer)
    before = counters()
    gc.collect()
    job.fit(it, watch)
    job.ready()
    t_close = time.perf_counter()
    if tracer is not None:
        tracer.close()
    after = counters()
    if watch.reads:
        run.log("metric: read %d times in the window, cross-entropy at the "
                "last read %.4f" % (len(watch.reads), watch.reads[-1]))
    result = {
        "t_open": it.t_open, "t_close": t_close,
        "callback_times": watch.times,
        "batches": len(watch.times), "rows_per_batch": job.batch,
        "batches_per_dispatch": job.k,
        "counters": {k: after[k] - before[k] for k in after},
        "program": program, "reference_side": job.reference_side(),
        "optimizer": job.optimizer,
        "work": {"layers": job.layers, "shape": job.shape,
                 "layout": job.layout, "dtype": job.dtype},
        "traced": None if tracer is None or tracer.started_at is None
        else {"from_batch": tracer.started_at, "to_batch": tracer.stopped_at},
    }
    return result
