"""Moonlight's layers through the program, held to the plain reference
(`benchmark/refs/moonlight.py`: float32, attention a masked softmax, every
held expert on every token, the balance loss a term of the loss, Adam
written out) at a tiny size on the CPU: latent attention with a value width
apart from the key width, the sigmoid router with its selection bias, the
shared experts and the leading dense layer, the model through `Module`
with recomputation and Adam on float32 master weights, the selection bias
after every step, the held experts' share of the whole layer, and each
assumption the configuration states."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from benchmark.refs import moonlight as ref
from mxnet_tpu.models import moonlight as model
from mxnet_tpu.ops import moe_ops

TINY = dict(
    vocab_size=256, hidden_size=64, num_attention_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
    intermediate_size=48, moe_intermediate_size=16, n_routed_experts=4,
    router_num_experts=8, first_held_expert=2, num_experts_per_tok=2,
    n_shared_experts=2, routed_scaling_factor=2.446, norm_topk_prob=True,
    scoring_func="sigmoid", first_k_dense_replace=1, num_hidden_layers=3,
    rms_norm_eps=1e-5, kv_norm_eps=1e-6, rope_theta=50000.0,
    bias_update_speed=0.001, aux_loss_alpha=1e-4)
ADAM = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}
CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                      "moonlight_16b_a3b_ep8_v8.json")


def _config():
    with open(CONFIG) as f:
        return json.load(f)


def _module(config=TINY, rows=2, tokens=64, seed=5, std=0.2,
            **symbol_args):
    sym = model.symbol(config, **symbol_args)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (rows, tokens))],
             label_shapes=[("softmax_label", (rows, tokens))],
             type_dict={"data": "int32"})
    w0 = {n: np.asarray(v) for n, v in
          ref.init_params(config, seed, {"std": std}).items()}
    mod.init_params(arg_params={n: mx.nd.array(v) for n, v in w0.items()})
    ids, labels = ref.make_pool(config, seed, 4, rows, tokens)
    return mod, w0, list(zip(np.asarray(ids), np.asarray(labels)))


def _batch(ids, labels):
    return mx.io.DataBatch(data=[mx.nd.array(ids, dtype="int32")],
                           label=[mx.nd.array(labels)])


def _leaf_gaps(got, want):
    return {n: float(np.linalg.norm(got[n] - want[n])
                     / max(np.linalg.norm(want[n]), 1e-30)) for n in want}


# -- the symbol ---------------------------------------------------------------

def test_symbol_and_reference_name_the_same_parameters():
    """The same parameters in the same order; the selection biases, one an
    expert layer, are the auxiliary states and zeros at the start."""
    sym = model.symbol(TINY)
    assert [n for n in sym.list_arguments()
            if n not in ("data", "softmax_label")] == list(
                ref.param_shapes(TINY))
    assert model.param_shapes(TINY) == ref.param_shapes(TINY)
    assert sym.list_auxiliary_states() == ref.bias_names(TINY) == [
        "l1_router_bias", "l2_router_bias"]
    mod, _, _ = _module()
    for bias in mod.get_params()[1].values():
        assert bias.shape == (8,) and not bias.asnumpy().any()


def test_scopes_name_the_blocks():
    """The profiler scopes the per-layer metrics read: the latent's part
    of attention inside ``attention``, the routed part, the shared
    experts, the dense layer and the head."""
    sym = model.symbol(TINY, dtype="bfloat16")
    scopes = {}
    for node in sym._topo_nodes():
        scope = node.attrs.get("__attrs__", {}).get("profiler_scope")
        if scope is not None and not node.is_var():
            scopes.setdefault(scope, set()).add(node.op)
    assert set(scopes) == {"attention", "attention/mla_latent", "dense_mlp",
                           "moe", "shared_experts", "lm_head"}
    assert "_contrib_flash_attention" in scopes["attention"] \
        or "flash_attention" in scopes["attention"]
    assert {"rope", "rms_norm", "Concat", "broadcast_to"} \
        <= scopes["attention/mla_latent"]
    assert "moe" in scopes["moe"]


@pytest.mark.parametrize("tokens", [64, 40])
def test_module_is_the_reference_in_float32(tokens):
    """Loss, softmax outputs and every leaf's gradient (of the
    cross-entropy plus the balance terms), at matrices of 0.2, ten times
    the configuration's, so that attention's scores and the routing differ
    from token to token."""
    mod, w0, batches = _module(tokens=tokens, recompute=True)
    ids, labels = batches[0]
    mod.forward_backward(_batch(ids, labels))
    probs = mod.get_outputs()[0].asnumpy()
    grads = {n: mod._exec.grad_dict[n].asnumpy() for n in mod._param_names}
    (_, (ce, want_probs, _)), want = jax.value_and_grad(
        ref.loss_fn, has_aux=True)(
        {n: jnp.asarray(v) for n, v in w0.items()}, ref.init_biases(TINY),
        jnp.asarray(ids), jnp.asarray(labels), TINY)
    assert np.abs(np.log(probs) - np.log(np.asarray(want_probs))).max() < 1e-4
    labels = labels.reshape(-1).astype(int)
    got_loss = -np.log(probs[np.arange(labels.size), labels]).mean()
    assert got_loss == pytest.approx(float(ce), rel=1e-5)
    gaps = _leaf_gaps(grads, {n: np.asarray(g) for n, g in want.items()})
    assert max(gaps.values()) < 1e-3, max(gaps, key=gaps.get)


def test_balance_loss_reaches_the_router_gradient():
    """Without the balance term the routers' gradients are the
    cross-entropy's alone: the term is in the gradient the symbol hands
    back, and the reference's with the factor set to 0 differs from it."""
    config = dict(TINY, aux_loss_alpha=0.5)
    mod, w0, batches = _module(config=config)
    ids, labels = batches[0]
    mod.forward_backward(_batch(ids, labels))
    got = mod._exec.grad_dict["l1_router_weight"].asnumpy()
    params = {n: jnp.asarray(v) for n, v in w0.items()}
    grads = {}
    for alpha in (0.5, 0.0):
        grads[alpha] = jax.grad(lambda p: ref.loss_fn(
            p, ref.init_biases(config), jnp.asarray(ids), jnp.asarray(labels),
            dict(config, aux_loss_alpha=alpha))[0])(params)["l1_router_weight"]
    np.testing.assert_allclose(got, grads[0.5], rtol=1e-3, atol=1e-7)
    assert np.linalg.norm(grads[0.5] - grads[0.0]) \
        > 0.01 * np.linalg.norm(grads[0.0])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_fit_steps_follow_the_reference(dtype):
    """`Module.fit` under Adam on the fused step with every layer
    recomputed: each step's loss, the parameters and first moments after
    three steps, and every layer's selection bias after EVERY step are the
    reference's (written-out Adam and the sign rule, the same policy)."""
    mod, w0, batches = _module(dtype=dtype, recompute=True, std=0.02)
    descs = ([mx.io.DataDesc("data", (2, 64), np.int32)],
             [mx.io.DataDesc("softmax_label", (2, 64), np.float32)])

    class Three(mx.io.DataIter):
        def __init__(self):
            super().__init__(2)
            self.provide_data, self.provide_label = descs
            self.n = 0

        def next(self):
            if self.n == 3:
                raise StopIteration
            self.n += 1
            return _batch(*batches[self.n - 1])

    losses, biases = [], []

    def after(param):
        losses.append(float(param.eval_metric.get()[1]))
        param.eval_metric.reset()
        biases.append({n: b.asnumpy() for n, b in
                       mod.get_params()[1].items()})

    mod.fit(Three(), eval_metric="ce", optimizer="adam",
            optimizer_params=dict(ADAM, wd=0.0, rescale_grad=1.0),
            num_epoch=1, batch_end_callback=after)
    assert mod._fused_plan not in (None, False)
    policy = "f32" if dtype == "float32" else "bf16"
    want = ref.follow(TINY, ADAM, {n: jnp.asarray(v) for n, v in w0.items()},
                      [(jnp.asarray(i), jnp.asarray(l))
                       for i, l in batches[:3]], policy)
    np.testing.assert_allclose(losses, want["losses"],
                               rtol=1e-5 if dtype == "float32" else 1e-3)
    assert len(biases) == 3
    for step, (got, exp) in enumerate(zip(biases, want["biases"]), 1):
        assert set(got) == set(exp)
        for name in exp:
            if dtype == "float32":
                np.testing.assert_array_equal(got[name], exp[name])
            else:
                # in bfloat16 a token near a tie may choose another
                # expert than the reference's, which can move a load
                # that sat on the mean: one step's move at most, for a
                # few of the experts
                off = np.abs(got[name] - exp[name])
                assert off.max() <= 1e-3 * step + 1e-7, (name, off)
                assert np.sum(off > 1e-7) <= 2, (name, off)
    assert any(np.abs(b).max() > 0 for b in biases[0].values())
    got = {n: v.asnumpy() for n, v in mod.get_params()[0].items()}
    means = {n: mod._updater.states[i][0].asnumpy()
             for i, n in enumerate(mod._param_names)}
    assert all(v.dtype == np.float32 for v in got.values())
    moved = lambda p: {n: np.asarray(p[n], "f") - w0[n] for n in w0}
    median = lambda a, b: float(np.median(list(_leaf_gaps(a, b).values())))
    host = lambda d: {n: np.asarray(v) for n, v in d.items()}
    if dtype == "float32":
        assert median(moved(got), moved(want["params"])) < 1e-3
        assert median(means, host(want["mean"])) < 1e-3
        return
    # bfloat16: at this size a token near a tie picks another expert now
    # and then, and the median leaf's change moves by several hundredths
    # between any two bfloat16 runs (the reference's own bfloat16 reads
    # 0.066 from its float32 on these seeds). The program is held to the
    # float32 reference no worse than the reference's bfloat16 is.
    exact = ref.follow(TINY, ADAM, {n: jnp.asarray(v) for n, v in w0.items()},
                       [(jnp.asarray(i), jnp.asarray(l))
                        for i, l in batches[:3]], "f32")
    for mine, theirs, full in (
            (moved(got), moved(want["params"]), moved(exact["params"])),
            (means, host(want["mean"]), host(exact["mean"]))):
        assert median(mine, full) <= 1.5 * median(theirs, full) < 0.1


def test_bias_holds_still_outside_training():
    """A forward pass that does not train (scoring, prediction) leaves the
    selection biases where they are."""
    mod, _, batches = _module()
    mod.forward(_batch(*batches[0]), is_train=False)
    for bias in mod.get_params()[1].values():
        assert not bias.asnumpy().any()


# -- the held experts' share --------------------------------------------------

def test_two_shares_add_up_to_the_uncut_layer():
    """Expert parallelism's share (the configuration's cut): the routed
    parts that the two devices holding experts 0-3 and 4-7 compute, with
    the shared experts (whole on every device) counted once, add up to the
    reference's layer with all eight experts held; the shares' moved
    biases are the same (every device routes over all the experts)."""
    rng = np.random.RandomState(3)
    f = lambda *s: jnp.asarray(0.3 * rng.randn(*s), jnp.float32)
    config = dict(TINY, n_routed_experts=8, first_held_expert=0)
    params = ref.init_params(config, 7, {"std": 0.3})
    p = "l1_"
    y = f(2, 24, 64) / 0.3
    bias = f(8)
    gate_up, down = (params[p + "experts_gate_up_weight"],
                     params[p + "experts_down_weight"])
    shared = ref.swiglu("f32", y, params[p + "shared_gate_up_weight"],
                        params[p + "shared_down_weight"])
    weight, _, _ = ref.routing(params, bias, p, y, config, "f32")
    whole = ref.experts(params, p, y, weight, "f32") + shared

    parts, moved = [], []
    for first in (0, 4):
        out, new = moe_ops.moe(
            y, y, params[p + "router_weight"], gate_up[first:first + 4],
            down[first:first + 4], bias, first_held=first, top_k=2,
            score="sigmoid", routed_scale=2.446, act="silu", bias_rate=1e-3)
        parts.append(out)
        moved.append(new)
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_array_equal(moved[0], moved[1])

    ref_parts = []
    for first in (0, 4):
        share = dict(config, n_routed_experts=4, first_held_expert=first)
        held = dict(params)
        held[p + "experts_gate_up_weight"] = gate_up[first:first + 4]
        held[p + "experts_down_weight"] = down[first:first + 4]
        w, _, _ = ref.routing(held, bias, p, y, share, "f32")
        ref_parts.append(ref.experts(held, p, y, w, "f32"))
    np.testing.assert_allclose(sum(ref_parts) + shared, whole, rtol=1e-5,
                               atol=1e-6)


# -- the configuration --------------------------------------------------------

def test_configuration_states_its_cut_and_assumptions():
    c = _config()
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 27,
                              "n_routed_experts": 64, "vocab_size": 163840}
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (6, 8, 20480)
    # published widths, the router's width and its experts a token
    assert (c["hidden_size"], c["kv_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["router_num_experts"],
            c["num_experts_per_tok"], c["n_shared_experts"]) \
        == (2048, 512, 128, 64, 128, 11264, 1408, 64, 6, 2)
    assert c["q_lora_rank"] is None and c["scoring_func"] == "sigmoid"
    assert "eight chips" in c["deployment"]
    for key in ("rotation", "kv_norm_eps", "bias_update_speed",
                "aux_loss_alpha", "init", "optimizer", "dtype", "recompute",
                "sequence"):
        assert key in c["assumed"], key


def test_published_widths_count_the_cut_parameters():
    """668,890,112 parameters: 9.97 GiB at 16 bytes a parameter."""
    shapes = ref.param_shapes(_config())
    count = lambda names: sum(int(np.prod(shapes[n])) for n in names)
    assert count(shapes) == 668_890_112
    assert count(n for n in shapes if n.startswith("l0_")) == 82_973_184
    assert count(n for n in shapes if n.startswith("l1_")) == 100_405_760
    assert count(["embed_weight", "head_weight"]) == 83_886_080
    assert count(shapes) * 16 / 2 ** 30 == pytest.approx(9.97, abs=0.01)


def test_init_is_normal_for_matrices_and_one_for_norms():
    params = ref.init_params(TINY, 11)
    for name, value in params.items():
        value = np.asarray(value)
        if name.endswith("norm_weight"):
            assert (value == 1).all(), name
        else:
            assert abs(value.std() - 0.02) < 0.004, name
            assert abs(value.mean()) < 0.004, name
