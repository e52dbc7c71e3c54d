"""Entry: `Module.fit` over token ids, as a language-model script calls it.

What `entries/fit.py` does, and with its classes (`PoolIter`, `Watch`,
`Tracer`, `counters`, `quiet`): one `Module` is bound, given the seeded
parameters and driven by a first `fit` over a few batches (the warm-up,
whose first steps are what `correct` compares); the SAME module then runs a
second `fit` of one epoch, the window. The pool holds seeded batches of ids
[rows, tokens] in host memory, the label of a position is the next id.

`run.py` owns the comparison (`compare.numbers`, written for SGD with
momentum: it works the first gradient out of the momentum after one step as
``g = -m1 / lr - wd w0``). This configuration runs Adam without decay,
whose first moment after one step is ``(1 - beta1) g``. Both sides
therefore hand over ``first_mom = -lr * mean1 / (1 - beta1)``, which that
algebra inverts to the very gradient the optimizer got; ``wd`` is 0.

The module is bound with an explicit ``spmd`` policy over the cell's own
chips (`SPMD`, below): a training job whose state fills the chip has to
say so, because only an explicit policy lets the fused step write the new
weights and gradients over the old ones; a plain `bind()` keeps the old
buffers alive for whoever holds views of them, which is the state twice
while a step runs, and 772 M parameters at 16 bytes then do not fit.

Mix parameters: as in `entries/fit.py`. Configuration keys this entry
reads: ``rows``, ``tokens``, ``optimizer``, ``init``, ``builder``,
``reference``.
"""
import gc
import importlib
import logging
import time
import warnings

import jax
import mxnet_tpu as mx
import numpy as np

FOLLOW = 3   # steps of the program that the reference follows
SPMD = "data_parallel"   # rows over the cell's chips, parameters whole


class FirstSteps:
    """batch_end_callback of the warm-up: every step's loss, the first
    step's softmax outputs and first gradient (from Adam's first moment,
    see the head of this file) and the parameters after ``follow`` steps,
    copied to host memory as they pass."""

    def __init__(self, mod, follow, optimizer):
        self.mod, self.follow, self.optimizer = mod, follow, optimizer
        self.losses, self.first_mom, self.params = [], None, None
        self.first_probs = None

    def __call__(self, param):
        self.losses.append(float(param.eval_metric.get()[1]))
        param.eval_metric.reset()
        n = len(self.losses)
        if n == 1:
            self.first_probs = np.asarray(
                self.mod.get_outputs()[0]._data).astype(np.float32)
            self.first_mom = first_mom(self.optimizer, {
                name: np.asarray(self.mod._updater.states[i][0]._data)
                for i, name in enumerate(self.mod._param_names)})
        if n == self.follow:
            args, _ = self.mod.get_params()
            self.params = {k: np.asarray(v._data) for k, v in args.items()}


def first_mom(optimizer, mean1):
    """Adam's first moment after one step in the form `compare.numbers`
    inverts: ``-lr * g`` with ``g = mean1 / (1 - beta1)``."""
    scale = np.float32(-optimizer["learning_rate"]
                       / (1.0 - optimizer["beta1"]))
    return {n: scale * np.asarray(m, np.float32) for n, m in mean1.items()}


class Job:
    """The cell, built from the seed: module, parameters, pool."""

    def __init__(self, run):
        self.run = run
        cfg, mix = run.config, run.traffic
        self.fit_entry = run.load("entries", "fit")
        self.k = int(mix["batches_per_dispatch"])
        if self.k != 1:
            raise SystemExit("fit_tokens reads the optimizer's state after "
                             "one step: one batch to a dispatch")
        self.follow = FOLLOW
        self.optimizer = cfg["optimizer"]
        chips = run.cell["chips"]
        self.rows, self.tokens = cfg["rows"] * chips, cfg["tokens"]
        self.shape = (self.rows, self.tokens)
        self.ref = importlib.import_module(
            "benchmark.refs." + cfg["reference"])
        self.shapes = self.ref.param_shapes(cfg)

        sym = run.load("models", cfg["builder"]).symbol(cfg)
        grown = [n for n in sym.list_arguments()
                 if n not in ("data", "softmax_label")]
        if sorted(grown) != sorted(self.shapes):
            raise SystemExit(
                "the program's symbol and the plain reference name different "
                "parameters: %s" % sorted(set(grown) ^ set(self.shapes))[:6])
        arg_shapes, _, _ = sym.infer_shape(data=self.shape,
                                           softmax_label=self.shape)
        for name, have in zip(sym.list_arguments(), arg_shapes):
            if name in self.shapes and tuple(have) != self.shapes[name]:
                raise SystemExit("%s: the program has %s, the reference %s"
                                 % (name, have, self.shapes[name]))
        contexts = [mx.tpu(i) for i in range(chips)]
        self.mod = mx.mod.Module(sym, context=contexts)
        self.mod.bind(data_shapes=[("data", self.shape)],
                      label_shapes=[("softmax_label", self.shape)],
                      type_dict={"data": "int32"},
                      spmd={"policy": SPMD,
                            "devices": [c.jax_device() for c in contexts]})

        params = self.ref.init_params(cfg, run.seed, cfg["init"])
        self.arg_params = {n: mx.nd.NDArray(v, contexts[0])
                           for n, v in params.items()}
        del params
        ids, labels = self.ref.make_pool(
            cfg, run.seed, int(mix["pool_batches"]), self.rows, self.tokens)
        ids, labels = np.asarray(ids), np.asarray(labels)
        self.pool = (ids, labels)
        self.batches = [
            mx.io.DataBatch(data=[mx.nd.array(x, ctx=mx.cpu(), dtype="int32")],
                            label=[mx.nd.array(y, ctx=mx.cpu())])
            for x, y in zip(ids, labels)]
        order = np.random.default_rng(run.seed).permutation(len(ids))
        self.order = [int(i) for i in order]
        self.descs = (
            [mx.io.DataDesc("data", self.shape, np.int32, "NT")],
            [mx.io.DataDesc("softmax_label", self.shape, np.float32, "NT")])
        run.log("built: %d parameters in %d leaves on %s, pool of %d batches "
                "of %d x %d ids in host memory, order %s"
                % (sum(int(np.prod(s)) for s in self.shapes.values()),
                   len(self.shapes), jax.devices()[0], len(ids), self.rows,
                   self.tokens, self.order))

    def fit(self, it, callback):
        opt = self.optimizer
        self.mod.fit(
            it, eval_metric="ce", optimizer=opt["name"],
            optimizer_params={
                "learning_rate": opt["learning_rate"], "beta1": opt["beta1"],
                "beta2": opt["beta2"], "epsilon": opt["epsilon"],
                "wd": opt["wd"], "rescale_grad": 1.0},
            arg_params=self.arg_params, allow_missing=False, num_epoch=1,
            batch_end_callback=callback, batches_per_dispatch=self.k)
        self.arg_params = None   # the module owns them from here on

    def iterator(self, **kwargs):
        return self.fit_entry.PoolIter(self.batches, self.order, self.descs,
                                       self.k, **kwargs)

    def ready(self):
        mx.nd.waitall()

    def first_steps(self):
        """The warm-up `fit`; returns what `compare.numbers` takes as the
        program's side."""
        warm = int(self.run.traffic["warmup_batches"])
        if warm < self.follow:
            raise SystemExit("warmup_batches must hold %d steps"
                             % self.follow)
        first = FirstSteps(self.mod, self.follow, self.optimizer)
        self.fit(self.iterator(limit=warm), first)
        self.ready()
        return {"losses": first.losses[:self.follow],
                "first_mom": first.first_mom, "params": first.params,
                "first_probs": first.first_probs}

    def reference_side(self):
        """A function that runs the plain reference over the same first
        batches from the same seeded parameters: float32 at ``highest``, or
        the ``policy`` it is given (``bf16``, or the control ``fp8``). To be
        called once the program's state is freed: the reference's own
        float32 parameters, moments and gradients are 12.4 GB."""
        import jax.numpy as jnp
        ref, cfg, opt = self.ref, self.run.config, self.optimizer
        seed = self.run.seed
        ids, labels = self.pool
        batches = [(ids[i], labels[i])
                   for i in (self.order * self.follow)[:self.follow]]

        def side(policy="f32"):
            gc.collect()
            w0 = ref.init_params(cfg, seed, cfg["init"])
            start = {n: np.asarray(v) for n, v in w0.items()}
            out = ref.follow(cfg, opt, w0, [(jnp.asarray(x), jnp.asarray(y))
                                            for x, y in batches], policy)
            del w0
            host = {"losses": out["losses"],
                    "first_probs": out["first_probs"],
                    "first_mom": first_mom(opt, out["first_mean"]),
                    "params": {n: np.asarray(v)
                               for n, v in out["params"].items()}}
            del out
            gc.collect()
            return host, start

        return side


def step_program_text(mod):
    """The compiled HLO text of the module's step program, for the readers
    that sum device time by named scope (`benchmark/scopes.py`). A traced
    run without it has nothing to say about the scopes, so a module that
    has no step program after a `fit` is an error here, not a silence."""
    return mod.step_program().compiled_text()


def quiet():
    """As `entries/fit.py`'s: `fit` called twice warns that it is bound and
    initialized already, and logs each epoch."""
    warnings.filterwarnings("ignore", message=".*already.*")
    warnings.filterwarnings("ignore", message=".*Already.*")
    logging.getLogger().setLevel(logging.ERROR)


def run(run):
    quiet()
    job = Job(run)     # loads `entries/fit.py`, whose classes drive the window
    fit_entry = job.fit_entry
    t1 = time.perf_counter()
    program = job.first_steps()
    run.log("warm-up: %d batches through fit in %.1f s, losses of the first "
            "%d steps %s" % (int(run.traffic["warmup_batches"]),
                             time.perf_counter() - t1, job.follow,
                             ["%.4f" % v for v in program["losses"]]))

    it = job.iterator(seconds=run.seconds)
    tracer = None
    if run.trace:
        tracer = fit_entry.Tracer(
            run.trace_dir, lambda: it.t_open, 0.4 * run.seconds,
            int(run.traffic["trace_batches"]), job.k)
    watch = fit_entry.Watch(int(run.traffic["metric_read_every"]), tracer)
    before = fit_entry.counters()
    gc.collect()
    job.fit(it, watch)
    job.ready()
    t_close = time.perf_counter()
    if tracer is not None:
        tracer.close()
    after = fit_entry.counters()
    if watch.reads:
        run.log("metric: read %d times in the window, cross-entropy at the "
                "last read %.4f" % (len(watch.reads), watch.reads[-1]))
    run.log("tokens: %.1f a second (%d batches of %d x %d in %.3f s)"
            % (len(watch.times) * job.rows * job.tokens
               / (t_close - it.t_open), len(watch.times), job.rows,
               job.tokens, t_close - it.t_open))
    return {
        "t_open": it.t_open, "t_close": t_close,
        "callback_times": watch.times,
        "batches": len(watch.times), "rows_per_batch": job.rows,
        "batches_per_dispatch": job.k,
        "counters": {k: after[k] - before[k] for k in after},
        "program": program, "reference_side": job.reference_side(),
        "optimizer": job.optimizer,
        "work": {"config": run.config, "rows": job.rows,
                 "tokens": job.tokens,
                 "hlo_text": step_program_text(job.mod) if run.trace
                 else None},
        "traced": None if tracer is None or tracer.started_at is None
        else {"from_batch": tracer.started_at, "to_batch": tracer.stopped_at},
    }
