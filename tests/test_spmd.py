"""SPMD sharded training on a named mesh (`parallel/spmd.py` +
`mxnet_tpu/compiled.py`).

Runs on the forced 8-device CPU mesh from conftest
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``). Covers: policy
spec construction, batch sharding along the 'data' axis, DP-vs-FSDP
(and tensor) numerical parity with the single-device fused step,
donation decisions, zero retraces after warmup via
``xla_stats.compile_counts()``, the in-program gradient sync replacing
the ``kvstore='tpu'`` post-step device sync, the FSDP per-shard memory
ledger win, the scaling-efficiency bench record + gate wiring, and the
"exactly one compiled-program implementation" structural assertion.
"""
import json
import os
import re
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compiled, telemetry, xla_stats
from mxnet_tpu.parallel import spmd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import bench_gate  # noqa: E402


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _make_data(n=256, d=20, k=3, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    W = rng.randn(d, k).astype(np.float32)
    y = X.dot(W).argmax(axis=1).astype(np.float32)
    return X, y


# ---------------------------------------------------------------------------
# Policy / mesh construction
# ---------------------------------------------------------------------------

def test_policy_spec_construction():
    from jax.sharding import PartitionSpec as P
    dp = spmd.make_policy("data_parallel")
    assert dp.mesh.axis_names == ("data",) and dp.data_size == 8
    assert dp.batch_spec() == P("data")
    assert dp.param_spec("w", (16, 8)) == P()

    fsdp = spmd.make_policy("fsdp")
    # largest dim divisible by 8 shards on 'data'
    assert fsdp.param_spec("w", (16, 8)) == P("data")
    assert fsdp.param_spec("w", (4, 24)) == P(None, "data")
    assert fsdp.param_spec("b", (16,)) == P("data")
    # nothing divisible -> replicated
    assert fsdp.param_spec("b", (3,)) == P()
    assert fsdp.param_spec("s", ()) == P()

    tp = spmd.make_policy("tensor", model_axis=2)
    assert tp.mesh.axis_names == ("data", "model")
    assert tp.data_size == 4 and tp.model_size == 2
    # output-unit (dim 0) sharding for FC-layout weights and biases
    assert tp.param_spec("fc_weight", (16, 8)) == P("model")
    assert tp.param_spec("fc_bias", (16,)) == P("model")
    # model-indivisible dim 0 falls back to the fsdp rule on 'data'
    assert tp.param_spec("odd", (3, 8)) == P(None, "data")

    with pytest.raises(ValueError, match="not one of|unknown"):
        spmd.make_policy("zeRO")
    with pytest.raises(ValueError, match="divisible"):
        dp.check_batch("data", (12, 4))


def test_named_mesh_cached_and_validated():
    import jax
    from mxnet_tpu.parallel.mesh import named_mesh
    devs = jax.devices()
    m1 = named_mesh(devs, {"data": 8})
    m2 = named_mesh(devs, {"data": 8})
    assert m1 is m2  # one Mesh object per layout (jit cache stability)
    with pytest.raises(ValueError, match="need 6 devices"):
        named_mesh(devs, {"data": 3, "model": 2})
    with pytest.raises(ValueError, match="duplicate"):
        named_mesh([devs[0], devs[0]], {"data": 2})


def test_resolve_forms():
    p = spmd.make_policy("fsdp")
    assert spmd.resolve(p) is p
    assert spmd.resolve("fsdp").name == "fsdp"
    d = spmd.resolve({"policy": "tensor", "model_axis": 4})
    assert d.name == "tensor" and d.model_size == 4
    with pytest.raises(ValueError, match="'policy' key"):
        spmd.resolve({"model_axis": 2})
    with pytest.raises(TypeError):
        spmd.resolve(42)


# ---------------------------------------------------------------------------
# Module binding: batch + param placement
# ---------------------------------------------------------------------------

def test_module_bind_places_batch_and_params():
    from jax.sharding import PartitionSpec as P
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (32, 20))],
             label_shapes=[("softmax_label", (32,))], spmd="fsdp")
    assert mod._spmd is not None and mod._spmd.name == "fsdp"
    # inputs shard along 'data'; params shard per policy
    assert mod._exec.arg_dict["data"]._data.sharding.spec == P("data")
    w = mod._exec.arg_dict["fc1_weight"]._data
    assert w.sharding.spec == P("data")
    assert len(w.sharding.device_set) == 8
    # gradient buffers inherit the parameter placement
    g = mod._exec.grad_dict["fc1_weight"]._data
    assert g.sharding.spec == P("data")


def test_module_env_default_policy(monkeypatch):
    monkeypatch.setenv("MXNET_SPMD", "fsdp")
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    mod.bind(data_shapes=[("data", (32, 20))],
             label_shapes=[("softmax_label", (32,))])
    assert mod._spmd.name == "fsdp"
    monkeypatch.setenv("MXNET_SPMD", "bogus")
    mod2 = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    with pytest.raises(Exception, match="MXNET_SPMD"):
        mod2.bind(data_shapes=[("data", (32, 20))],
                  label_shapes=[("softmax_label", (32,))])


def test_module_rejects_indivisible_batch():
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    with pytest.raises(Exception, match="divisible"):
        mod.bind(data_shapes=[("data", (30, 20))],
                 label_shapes=[("softmax_label", (30,))], spmd="fsdp")


# ---------------------------------------------------------------------------
# Numerical parity: single-device fused step vs DP vs FSDP vs tensor
# ---------------------------------------------------------------------------

def _train(spmd_arg, epochs=4, kvstore="tpu"):
    X, y = _make_data()
    it = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=False,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             spmd=spmd_arg)
    mx.random.seed(7)
    np.random.seed(7)
    mod.init_params(initializer=mx.init.Xavier(rnd_type="uniform",
                                               factor_type="avg",
                                               magnitude=2))
    mod.init_optimizer(kvstore=kvstore, optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.5),
                                         ("momentum", 0.9)))
    metric = mx.metric.Accuracy()
    accs = []
    for _ in range(epochs):
        it.reset()
        metric.reset()
        for batch in it:
            mod._step(batch)
            mod.update_metric(metric, batch.label)
        accs.append(metric.get()[1])
    args, _ = mod.get_params()
    return accs, {n: a.asnumpy() for n, a in args.items()}, mod


def test_dp_and_fsdp_match_single_device_fused_step():
    accs1, args1, _ = _train(None)          # single-device fused step
    accs_dp, args_dp, _ = _train("data_parallel")
    accs_fs, args_fs, _ = _train("fsdp")
    assert accs_dp == pytest.approx(accs1, abs=1e-3)
    assert accs_fs == pytest.approx(accs1, abs=1e-3)
    for name in args1:
        np.testing.assert_allclose(args_dp[name], args1[name],
                                   rtol=2e-4, atol=2e-5, err_msg=name)
        np.testing.assert_allclose(args_fs[name], args1[name],
                                   rtol=2e-4, atol=2e-5, err_msg=name)
    assert accs1[-1] > 0.8  # and it actually learns


def test_tensor_policy_matches_single_device():
    accs1, args1, _ = _train(None, epochs=3)
    accs_tp, args_tp, mod = _train({"policy": "tensor", "model_axis": 2},
                                   epochs=3)
    assert mod._spmd.model_size == 2
    assert accs_tp == pytest.approx(accs1, abs=1e-3)
    for name in args1:
        np.testing.assert_allclose(args_tp[name], args1[name],
                                   rtol=5e-4, atol=5e-5, err_msg=name)


# ---------------------------------------------------------------------------
# Gradient sync lives INSIDE the compiled step (kvstore='tpu')
# ---------------------------------------------------------------------------

def test_kvstore_tpu_has_no_post_step_sync():
    push0 = telemetry.counter("kvstore_push_total").value
    pull0 = telemetry.counter("kvstore_pull_total").value
    _, _, mod = _train("fsdp", epochs=2, kvstore="tpu")
    # no kvstore was even created: the in-program collective subsumed it
    assert mod._kvstore is None and not mod._update_on_kvstore
    assert telemetry.counter("kvstore_push_total").value == push0
    assert telemetry.counter("kvstore_pull_total").value == pull0


# ---------------------------------------------------------------------------
# Zero retraces / cold compiles at steady state
# ---------------------------------------------------------------------------

def test_zero_retraces_after_warmup():
    X, y = _make_data(n=128)
    it = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=False,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             spmd="fsdp")
    mod.init_params()
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),))
    batches = list(it)
    mod._step(batches[0])   # warmup: the one compile
    c0 = xla_stats.compile_counts()
    for _ in range(3):
        for b in batches:
            mod._step(b)
    c1 = xla_stats.compile_counts()
    assert c1["compiles"] == c0["compiles"], "cold compile at steady state"
    assert c1["retraces"] == c0["retraces"], "retrace at steady state"
    assert c1["cache_hits"] > c0["cache_hits"]


def test_compiled_program_warmup_prepopulates_cache():
    import jax.numpy as jnp
    prog = compiled.tracked_jit(lambda x: x * 2, "spmd.test.warmup")
    prog.warmup(jnp.ones(4))
    c0 = xla_stats.compile_counts()
    out = prog(jnp.ones(4))
    np.testing.assert_allclose(np.asarray(out), 2.0)
    c1 = xla_stats.compile_counts()
    assert c1["compiles"] == c0["compiles"]          # no new compile
    assert c1["cache_hits"] == c0["cache_hits"] + 1  # served from cache


# ---------------------------------------------------------------------------
# Donation decisions
# ---------------------------------------------------------------------------

class _FakeAccel:
    device_type = "tpu"


@pytest.mark.parametrize("policy", ["data_parallel", "fsdp"])
def test_a_mesh_of_one_named_device_is_the_single_device_executor(policy):
    """An option dict's ``devices`` names the mesh whatever else is visible
    (8 devices here). One device lays nothing out: the module runs the
    single-device executor (one sharding name for its buffers, no retrace
    between two `fit`s), and the explicit choice keeps the donation it
    unlocks; the numbers are the plain fused step's."""
    import jax
    from jax.sharding import SingleDeviceSharding
    one = jax.devices()[0]
    accs, args, mod = _train({"policy": policy, "devices": [one]}, epochs=2)
    assert mod._spmd is None and mod._spmd_explicit
    w = mod._exec.arg_dict["fc1_weight"]._data
    assert isinstance(w.sharding, SingleDeviceSharding)
    assert w.sharding.device_set == {one}
    assert mod.step_program().policy is None
    accs1, args1, _ = _train(None, epochs=2)
    assert accs == pytest.approx(accs1, abs=1e-6)
    for name in args1:
        np.testing.assert_allclose(args[name], args1[name], atol=1e-6)


def test_step_program_is_the_fused_step_and_has_its_compiled_text():
    """`Module.step_program()`: None until a step has run, then the
    `CompiledProgram` `_step` dispatches, whose `compiled_text()` is the
    optimized HLO of each executable it holds (what a device trace names
    its events by)."""
    X, y = _make_data(n=64)
    it = mx.io.NDArrayIter(X, y, batch_size=32, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd")
    assert mod.step_program() is None
    mod._step(next(iter(it)))
    program = mod.step_program()
    assert program is mod._fused_plan[3]
    texts = program.compiled_text()
    assert len(texts) == 1 and "HloModule" in texts[0]
    assert re.search(r'op_name="[^"]*optimizer', texts[0])


def test_donation_decision(monkeypatch):
    # accelerators donate, CPU backends don't (no donation support)
    assert compiled.donate_argnums_for(_FakeAccel(), (0, 7)) == (0, 7)
    assert compiled.donate_argnums_for(mx.cpu(), (0, 7)) == ()
    # MXNET_SPMD_DONATE=0 revokes only the SPMD-unlocked param donation;
    # the legacy device decision is untouched by it
    assert compiled.spmd_donate_enabled()
    monkeypatch.setenv("MXNET_SPMD_DONATE", "0")
    assert not compiled.spmd_donate_enabled()
    assert compiled.donate_argnums_for(_FakeAccel(), (7,)) == (7,)


def test_spmd_fused_step_donates_params_on_accelerators(monkeypatch):
    """An EXPLICITLY selected SPMD policy frees the old param + optimizer
    buffers via donate_argnums (grad_args is arg 0, state_vals arg 7);
    the implicit multi-device default keeps the legacy guarantee (params
    never donated — user code may hold views). Asserted through the
    decision the plan applies — on the CPU test mesh the set is
    stripped to ()."""
    _, _, mod = _train("fsdp", epochs=1)
    assert mod._fused_plan is not False
    assert mod._spmd_explicit  # spmd= was passed
    step_fn = mod._fused_plan[3]
    assert step_fn.donate_argnums == ()  # CPU: stripped by the decision
    # the compiled program carries the policy (mesh-scoped dispatch)
    assert step_fn.policy is mod._spmd
    # the decision itself, on an accelerator, donates params + states
    assert compiled.donate_argnums_for(_FakeAccel(), (0, 7)) == (0, 7)
    # a multi-device context WITHOUT spmd= keeps params un-donated
    X, y = _make_data(n=64)
    it = mx.io.NDArrayIter(X, y, batch_size=32, label_name="softmax_label")
    mod2 = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    assert mod2._spmd is not None and not mod2._spmd_explicit


# ---------------------------------------------------------------------------
# FSDP memory win: per-shard ledger under a single-device budget
# ---------------------------------------------------------------------------

def test_fsdp_fits_model_past_single_device_budget():
    """A model whose REPLICATED param+optimizer bytes exceed a (synthetic)
    single-device budget trains under the fsdp policy, and the per-shard
    ledger proves the memory win: each device holds ~1/8 of the state."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=512, name="big1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="big2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    X = np.random.RandomState(0).randn(64, 256).astype(np.float32)
    y = (np.random.RandomState(1).rand(64) * 8).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=32, label_name="softmax_label")

    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             spmd="fsdp")
    mod.init_params()
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),
                                         ("momentum", 0.9)))
    for batch in it:
        mod._step(batch)

    params_global = xla_stats.tree_bytes(
        [mod._exec.arg_dict[n] for n in mod._param_names])
    led = xla_stats.ledger()
    scope = mod._ledger_scope()
    shard_params = led[(scope, "params")]
    shard_opt = led[(scope, "optimizer")]
    # momentum state mirrors the params: replicated footprint is 2x
    replicated_total = 2 * params_global
    budget = replicated_total // 2   # a device that CANNOT hold it all
    assert replicated_total > budget
    assert shard_params + shard_opt < budget, \
        "per-shard bytes do not fit the budget the replicated state blew"
    # the dominant (512, 256) weight shards 8 ways; small params stay
    # replicated, so the shard total sits well under a quarter of global
    assert shard_params < params_global / 4
    out = mod.get_outputs()[0].asnumpy()
    assert np.isfinite(out).all()


def test_tree_shard_bytes_replicated_equals_global():
    import jax
    import jax.numpy as jnp
    arrs = [jnp.zeros((16, 8), jnp.float32), jnp.zeros((5,), jnp.float32)]
    assert xla_stats.tree_shard_bytes(arrs) == xla_stats.tree_bytes(arrs)
    pol = spmd.make_policy("fsdp")
    sharded = jax.device_put(jnp.zeros((16, 8), jnp.float32),
                             pol.param_sharding("w", (16, 8)))
    assert xla_stats.tree_shard_bytes([sharded]) == sharded.nbytes // 8


# ---------------------------------------------------------------------------
# Gluon Trainer spmd
# ---------------------------------------------------------------------------

def test_gluon_trainer_spmd_places_params():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.gluon import nn, Trainer
    net = nn.Dense(16, in_units=24)
    net.initialize()
    net.hybridize()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1}, spmd="fsdp")
    w = net.weight.data()._data
    # weight (16, 24): the largest divisible dim (24, dim 1) shards
    assert w.sharding.spec == P(None, "data")
    assert len(w.sharding.device_set) == 8
    from mxnet_tpu import autograd
    x = trainer.place_batch(mx.nd.ones((8, 24)))
    assert x._data.sharding.spec == P("data")
    with autograd.record():
        out = net(x)
        loss = out.sum()
    loss.backward()
    trainer.step(batch_size=8)
    assert np.isfinite(net.weight.data().asnumpy()).all()
    # per-shard ledger recorded under this trainer's own scope
    led = xla_stats.ledger()
    scope = trainer._ledger_scope
    assert scope.startswith("gluon_trainer")
    assert led[(scope, "params")] > 0
    assert led[(scope, "params")] < xla_stats.tree_bytes(
        [p.data() for p in net.collect_params().values()])


def test_rng_chain_advances_for_sharded_anchors():
    """A policy-sharded param used as the RNG placement anchor must
    advance the SAME per-mesh replicated chain every call — reading one
    cache entry while writing another would freeze the key (identical
    dropout masks every step)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import random as mxrand
    pol = spmd.make_policy("fsdp")
    anchor = jax.device_put(jnp.zeros((16, 8), jnp.float32),
                            pol.param_sharding("w", (16, 8)))
    k1 = np.asarray(mxrand.next_key_like(anchor))
    k2 = np.asarray(mxrand.next_key_like(anchor))
    k3 = np.asarray(mxrand.next_key_like(anchor))
    assert not np.array_equal(k1, k2) and not np.array_equal(k2, k3)
    # a replicated anchor over the same mesh continues the same chain
    repl = jax.device_put(jnp.zeros((8,), jnp.float32), pol.replicated())
    k4 = np.asarray(mxrand.next_key_like(repl))
    assert not np.array_equal(k3, k4)


# ---------------------------------------------------------------------------
# Scaling-efficiency record + gate wiring
# ---------------------------------------------------------------------------

def test_scaling_efficiency_record():
    sys.path.insert(0, REPO)
    import __graft_entry__ as graft
    rec = graft.scaling_efficiency_record(8, batch_per_device=8, steps=2)
    assert rec["metric"] == "multichip_scaling_efficiency"
    assert rec["n_devices"] == 8 and rec["unit"] == "ratio"
    assert rec["value"] > 0 and rec["one_device_rate"] > 0


def test_multichip_gate_direction_and_history(tmp_path):
    d = str(tmp_path)
    hist_line = json.dumps({"metric": bench_gate.MULTICHIP_METRIC,
                            "value": 0.9, "n_devices": 8})
    with open(os.path.join(d, "MULTICHIP_r01.json"), "w") as fh:
        json.dump({"n_devices": 8, "ok": True, "tail": hist_line + "\n"},
                  fh)
    hist = bench_gate.load_history(d)
    assert bench_gate.MULTICHIP_METRIC in hist  # MULTICHIP rounds parse
    ok = [{"metric": bench_gate.MULTICHIP_METRIC, "value": 0.85}]
    bad = [{"metric": bench_gate.MULTICHIP_METRIC, "value": 0.5}]
    assert bench_gate.gate_records(
        ok, history_dir=d, metric=bench_gate.MULTICHIP_METRIC) == 0
    assert bench_gate.gate_records(
        bad, history_dir=d, metric=bench_gate.MULTICHIP_METRIC) == 1


def test_repo_gate_picks_up_multichip(tmp_path, monkeypatch, capsys):
    """repo_gate --bench gates the scaling metric when MULTICHIP records
    are present in the run output."""
    import repo_gate
    run = tmp_path / "run.jsonl"
    run.write_text(json.dumps({"metric": bench_gate.MULTICHIP_METRIC,
                               "value": 0.8}) + "\n")
    rc = repo_gate.main(["--bench", str(run)])
    out = capsys.readouterr().out
    # analysis gate ran, and the multichip metric was gated (skip or
    # pass against repo history — older MULTICHIP rounds carry no tail)
    assert '"mxanalyze_gate"' in out
    assert out.count('"bench_gate"') >= 2  # train headline + multichip
    assert rc == 0


# ---------------------------------------------------------------------------
# Structural: exactly ONE compiled-program implementation
# ---------------------------------------------------------------------------

def test_single_compiled_program_layer():
    """The acceptance grep: the signature->executable cache / AOT warmup
    machinery exists once (mxnet_tpu/compiled.py); the five former
    tracked_jit call sites are thin clients of it, and xla_stats only
    aliases the names."""
    root = os.path.join(REPO, "mxnet_tpu")
    impl_re = re.compile(
        r"^\s*(?:class\s+(?:CompiledProgram|TrackedJit)\b"
        r"|def\s+_compile_entry\b)", re.M)
    owners = []
    for dirpath, _dirs, files in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            src = open(path, encoding="utf-8").read()
            if impl_re.search(src):
                owners.append(os.path.relpath(path, REPO))
    assert owners == ["mxnet_tpu/compiled.py"], \
        "compiled-program machinery leaked outside compiled.py: %s" % owners

    # the five client call sites all go through mxnet_tpu.compiled
    clients = ["mxnet_tpu/executor.py", "mxnet_tpu/module/module.py",
               "mxnet_tpu/gluon/block.py",
               "mxnet_tpu/parallel/data_parallel.py"]
    for rel in clients:
        src = open(os.path.join(REPO, rel), encoding="utf-8").read()
        assert "compiled" in src and "xla_stats.tracked_jit" not in src, \
            "%s is not a CompiledProgram client" % rel

    # xla_stats only aliases: its tracked_jit body delegates to compiled
    xs = open(os.path.join(REPO, "mxnet_tpu/xla_stats.py"),
              encoding="utf-8").read()
    assert "compiled.tracked_jit" in xs
    assert "self._fn.lower(" not in xs  # no AOT machinery left behind
