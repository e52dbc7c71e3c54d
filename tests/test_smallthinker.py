"""SmallThinker's layers through the program, held to the plain reference
(`benchmark/refs/smallthinker.py`: float32, attention a masked softmax,
every held expert on every token, Adam written out) at a tiny size on the
CPU: the rotary term, the sliding window, the held experts' layer and its
share of the whole layer, the model through `Module` with recomputation and
Adam on float32 master weights, and each assumption the configuration
states."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from benchmark.refs import smallthinker as ref
from mxnet_tpu.models import smallthinker as model
from mxnet_tpu.ops import moe_ops

TINY = dict(
    vocab_size=96, hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, moe_num_primary_experts=2,
    moe_router_num_experts=8, moe_first_held_expert=2,
    moe_num_active_primary_experts=3, moe_ffn_hidden_size=32,
    norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000.0,
    num_hidden_layers=4, sliding_window_layout=[0, 1, 1, 1],
    rope_layout=[0, 1, 1, 1], sliding_window_size=12)
ADAM = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}
CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                      "smallthinker_21b_a3b_ep4_v8.json")


def _config():
    with open(CONFIG) as f:
        return json.load(f)


# -- the held experts' layer --------------------------------------------------

def _layer_inputs(seed=0, tokens=24, experts=8, dim=16, width=8):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    return (f(2, tokens, dim), f(2, tokens, dim), f(experts, dim),
            0.3 * f(experts, 2 * width, dim), 0.3 * f(experts, dim, width))


def _dense_layer(router_x, x, router_w, gate_up, down, first, top_k):
    """Every held expert on every token, weighted by its routing weight."""
    probs = jax.nn.softmax(router_x @ router_w.T, axis=-1)
    top, chosen = jax.lax.top_k(probs, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    width = gate_up.shape[1] // 2
    out = jnp.zeros(x.shape, jnp.float32)
    for i in range(gate_up.shape[0]):
        w = jnp.sum(jnp.where(chosen == first + i, top, 0.0), axis=-1)
        both = x @ gate_up[i].T
        out = out + w[..., None] * (
            (jax.nn.relu(both[..., :width]) * both[..., width:]) @ down[i].T)
    return out


def _held(args, first, held):
    router_x, x, router_w, gate_up, down = args
    return (router_x, x, router_w, gate_up[first:first + held],
            down[first:first + held])


@pytest.mark.parametrize("first,held,top_k", [
    (0, 8, 3), (2, 2, 3), (6, 2, 2), (4, 4, 1)])
def test_held_experts_are_the_dense_layer(first, held, top_k):
    """Output and all five inputs' gradients against every held expert
    computed on every token: the sort, the grouped products and the
    weighted gather change no value."""
    args = _held(_layer_inputs(), first, held)
    w = jnp.asarray(np.random.RandomState(9).randn(2, 24, 16), jnp.float32)
    op = lambda *a: moe_ops.moe(*a, first_held=first, top_k=top_k)
    dense = lambda *a: _dense_layer(*a, first, top_k)
    np.testing.assert_allclose(op(*args), dense(*args), rtol=1e-5,
                               atol=1e-5)
    grads = [jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=range(5))(
        *args) for fn in (op, dense)]
    for got, want in zip(*grads):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_skewed_routing_drops_nothing():
    """A router that sends every token to the same experts, all of them
    held: every one of the tokens x top_k assignments is computed (there is
    no capacity), and the result is the dense layer's. The same router
    seen from a device that holds none of them gives zero."""
    router_x, x, router_w, gate_up, down = _layer_inputs(tokens=40)
    # every token leans the same way, and experts 2, 3, 4 follow it
    lean = jnp.ones((16,), jnp.float32)
    router_x = 0.1 * router_x + lean
    router_w = (0.1 * router_w).at[2:5].add(
        jnp.asarray([[1.0], [0.9], [0.8]], jnp.float32) * lean)
    _, chosen = jax.lax.top_k(router_x @ router_w.T, 3)
    assert set(np.unique(np.asarray(chosen))) == {2, 3, 4}
    args = (router_x, x, router_w, gate_up[2:6], down[2:6])
    got = moe_ops.moe(*args, first_held=2, top_k=3)
    np.testing.assert_allclose(got, _dense_layer(*args, 2, 3), rtol=1e-5,
                               atol=1e-5)
    assert float(jnp.min(jnp.linalg.norm(got, axis=-1))) > 0
    away = moe_ops.moe(router_x, x, router_w, gate_up[5:7], down[5:7],
                       first_held=5, top_k=3)
    assert float(jnp.max(jnp.abs(away))) == 0.0


def test_four_shares_add_up_to_the_uncut_layer():
    """Expert parallelism's share (the configuration's cut): the parts that
    the four devices holding experts 0-1, 2-3, 4-5 and 6-7 compute add up
    to the layer with all eight experts, in the program and in the
    reference alike."""
    args = _layer_inputs()
    whole = moe_ops.moe(*args, first_held=0, top_k=3)
    shares = [moe_ops.moe(*_held(args, first, 2), first_held=first, top_k=3)
              for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(shares), whole, rtol=1e-5, atol=1e-5)

    config = dict(TINY, moe_num_primary_experts=2, moe_ffn_hidden_size=8)
    params = {"l0_router_weight": args[2]}
    parts = []
    for first in (0, 2, 4, 6):
        share = dict(config, moe_first_held_expert=first)
        params["l0_experts_gate_up_weight"] = args[3][first:first + 2]
        params["l0_experts_down_weight"] = args[4][first:first + 2]
        weight = ref.routing(params, "l0_", args[0], share, "f32")
        parts.append(ref.experts(params, "l0_", args[1], weight, share,
                                 "f32"))
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-5)


def test_the_op_refuses_a_share_outside_the_router():
    args = [mx.nd.array(np.asarray(a)) for a in _held(_layer_inputs(), 6, 2)]
    with pytest.raises(mx.MXNetError, match="held from 7"):
        mx.nd.moe(*args, first_held=7, top_k=3)
    with pytest.raises(mx.MXNetError, match="top_k 9"):
        mx.nd.moe(*args, first_held=6, top_k=9)


def _results(text, op):
    """The result types (``tensor<AxBx..>``) of every ``op`` in a lowered
    StableHLO text."""
    out, i = [], text.find('"stablehlo.%s"(' % op)
    while i >= 0:
        out.append(re.search(r"\) -> (tensor<[^>]*>)", text[i:]).group(1))
        i = text.find('"stablehlo.%s"(' % op, i + 1)
    return out


def test_rows_move_by_gathers_and_one_sort():
    """The layer's gradient moves rows by gathers both ways: no scatter
    into the buffer of assignments or into the tokens (a result whose last
    axis is the hidden width; top-k's own, over the router's experts, is
    left), and the assignments are sorted once."""
    args = _held(_layer_inputs(dim=24), 2, 4)

    def loss(*a):
        return jnp.sum(moe_ops.moe(*a, first_held=2, top_k=3))

    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(*args).as_text()
    scatters = _results(text, "scatter")
    assert not [t for t in scatters if t.endswith("x24xf32>")], scatters
    assert len(_results(text, "sort")) == 1


def test_rank_inverts_the_sorted_order():
    """The sort-free ``rank`` is the inverse of ``order`` (`argsort`), and
    ``order`` the stable sort of the keys, for assignments that include a
    token with no held slot and a token with every slot held."""
    rng = np.random.RandomState(4)
    tokens, top_k, experts, first, held = 50, 4, 12, 3, 5
    chosen = np.stack([rng.choice(experts, top_k, replace=False)
                       for _ in range(tokens)])
    chosen[0] = [0, 1, 2, 11]        # none held
    chosen[1] = [3, 4, 6, 7]         # every slot held
    local = chosen - first
    key = np.where((local >= 0) & (local < held), local, held).reshape(-1)
    order, rank, counts = moe_ops.permutation(jnp.asarray(key, jnp.int32),
                                              held + 1)
    np.testing.assert_array_equal(order, np.argsort(key, kind="stable"))
    np.testing.assert_array_equal(rank, np.argsort(np.asarray(order)))
    np.testing.assert_array_equal(counts, np.bincount(key, minlength=held + 1))


@pytest.mark.tpu
def test_held_experts_are_the_dense_layer_on_the_chip():
    """On the chip, where the grouped products are XLA's own kernels (the
    CPU decomposes them and cannot show their faults): the layer inside
    `jax.jit`, on bfloat16 operands, against its dense form in float32 at
    ``highest``, output and all five gradients, each within 3 % by norm
    (bfloat16's rounding; a kernel that computes the wrong rows is off by
    the order of the values)."""
    if jax.devices()[0].platform != "tpu":
        pytest.skip("no TPU visible")
    router_x, x, router_w, gate_up, down = _layer_inputs(
        tokens=256, experts=16, dim=256, width=128)
    args = (router_x, x.astype(jnp.bfloat16), router_w,
            gate_up.astype(jnp.bfloat16), down.astype(jnp.bfloat16))
    held = _held(args, 4, 8)
    w = jnp.asarray(np.random.RandomState(9).randn(2, 256, 256), jnp.float32)

    def op(*a):
        return moe_ops.moe(*a, first_held=4, top_k=4).astype(jnp.float32)

    def dense(*a):
        with jax.default_matmul_precision("highest"):
            return _dense_layer(*[v.astype(jnp.float32) for v in a], 4, 4)

    def value_and_grads(fn):
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            lambda *b: jnp.sum(fn(*b) * w), argnums=range(5))(*a)))(*held)

    (got, got_grads), (want, want_grads) = map(value_and_grads, (op, dense))
    for g, v in [(got, want)] + list(zip(got_grads, want_grads)):
        g, v = np.asarray(g, np.float32), np.asarray(v, np.float32)
        assert np.isfinite(g).all()
        assert np.linalg.norm(g - v) < 0.03 * np.linalg.norm(v)


# -- the assumptions the configuration states ---------------------------------

def test_rope_turns_each_half_pair_over_the_whole_head():
    """Rotate-half over all of the head: position t turns (x_i, x_(i+D/2))
    by t * theta^(-2i/D), the last pair too; position 0 is left as it is;
    the reference's rotary term is the op's."""
    rng = np.random.RandomState(2)
    x = rng.randn(1, 7, 3, 16).astype("f")
    got = mx.nd.rope(mx.nd.array(x), theta=1.5e6).asnumpy()
    t = np.arange(7)[:, None, None]
    angle = t * 1.5e6 ** (-np.arange(8) * 2.0 / 16)[None, None]
    first, second = x[0, ..., :8], x[0, ..., 8:]
    want = np.concatenate([first * np.cos(angle) - second * np.sin(angle),
                           second * np.cos(angle) + first * np.sin(angle)],
                          axis=-1)
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[0, 0], x[0, 0])
    np.testing.assert_allclose(np.asarray(ref.rotary(jnp.asarray(x), 1.5e6)),
                               got, rtol=1e-5, atol=1e-5)


def test_a_window_counts_the_query_itself():
    """``window`` keys include the query: a window of one sees the query's
    own value alone; a window of two, the query and the key before it."""
    from mxnet_tpu.ops.pallas_kernels import flash_attention
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(1, 10, 2, 8), jnp.float32)
               for _ in range(3))
    own = flash_attention(q, k, v, causal=True, window=1, block_q=8,
                          block_k=8)
    np.testing.assert_allclose(own, v, rtol=1e-5, atol=1e-6)
    two = flash_attention(q, k, v, causal=True, window=2, block_q=8,
                          block_k=8)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8)
    i, j = np.arange(10)[:, None], np.arange(10)[None]
    p = jax.nn.softmax(jnp.where((j <= i) & (i - j < 2), s, -jnp.inf), -1)
    np.testing.assert_allclose(two, jnp.einsum("bhqk,bkhd->bqhd", p, v),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=4)


def _node(graph, index):
    return graph["nodes"][index]


def _norm_weight(graph, index):
    """The weight's name of the rms_norm node ``index``."""
    node = _node(graph, index)
    assert node["op"] == "rms_norm"
    return _node(graph, node["inputs"][1][0])["name"]


def test_the_router_reads_the_layer_input_before_attention():
    """Every layer's `moe` node takes the norm of the layer's input (the
    one attention reads) as the router's input, and the norm after
    attention as the experts' input."""
    graph = json.loads(model.symbol(TINY, dtype="bfloat16").tojson())
    moes = [n for n in graph["nodes"] if n["op"] == "moe"]
    assert len(moes) == TINY["num_hidden_layers"]
    for i, node in enumerate(moes):
        router_in, expert_in = node["inputs"][0][0], node["inputs"][1][0]
        assert _norm_weight(graph, router_in) == "l%d_input_norm_weight" % i
        assert _norm_weight(graph, expert_in) == "l%d_post_norm_weight" % i
        attrs = node.get("attrs", node.get("param", {}))
        assert attrs["first_held"] == "2" and attrs["top_k"] == "3"


def test_window_and_rotary_follow_the_layouts():
    """Layer 0 (layouts 0): no rotary term and no window; layers 1-3: both,
    the window the configuration's."""
    graph = json.loads(model.symbol(TINY).tojson())
    attn = [n for n in graph["nodes"]
            if n["op"] in ("_contrib_flash_attention", "flash_attention")]
    windows = [n.get("attrs", {}).get("window") for n in attn]
    assert windows == [None, "12", "12", "12"]
    assert sum(n["op"] == "rope" for n in graph["nodes"]) == 6


def test_configuration_states_its_cut_and_assumptions():
    """Every width is the published one, the cut keys are listed in
    ``reduced`` beside their published values, and each point the source
    leaves open is an ``assumed`` line."""
    c = _config()
    assert (c["hidden_size"], c["head_dim"], c["num_attention_heads"],
            c["num_key_value_heads"], c["moe_ffn_hidden_size"],
            c["moe_num_active_primary_experts"], c["sliding_window_size"],
            c["moe_router_num_experts"]) == (2560, 128, 28, 4, 768, 6, 4096,
                                             64)
    assert set(c["reduced"]) == set(c["published"])
    assert c["moe_num_primary_experts"] * 4 == c["moe_router_num_experts"]
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    for key in ("router_input", "rope", "window", "experts", "init",
                "optimizer"):
        assert key in c["assumed"]
    assert "secondary" in c["assumed"]["experts"]
    granite = json.load(open(CONFIG.replace(
        "smallthinker_21b_a3b_ep4_v8", "granite_4_0_h_micro_pp4_vp8")))
    assert c["optimizer"] == granite["optimizer"]
    names = model.param_shapes(c)
    assert not [n for n in names if "shared" in n or "secondary" in n]


def test_published_widths_count_the_cut_parameters():
    """One period at published widths, 16 of 64 experts, an eighth of the
    vocabulary: 559,290,880 parameters, 115,512,320 a layer."""
    c = _config()
    shapes = ref.param_shapes(c)
    assert model.param_shapes(c) == shapes
    assert sum(int(np.prod(s)) for s in shapes.values()) == 559290880
    layer = sum(int(np.prod(s)) for n, s in shapes.items()
                if n.startswith("l0_"))
    assert layer == 115512320
    assert shapes["l0_experts_gate_up_weight"] == (16, 1536, 2560)
    assert shapes["l0_experts_down_weight"] == (16, 2560, 768)


def test_init_is_normal_for_matrices_and_one_for_norms():
    params = ref.init_params(TINY, 11)
    for name, value in params.items():
        value = np.asarray(value)
        if name.endswith("norm_weight"):
            assert (value == 1).all(), name
        else:
            assert abs(value.std() - 0.02) < 0.004, name
            assert abs(value.mean()) < 0.004, name


# -- the model through Module -------------------------------------------------

def _module(config=TINY, rows=2, tokens=32, seed=5, std=0.2,
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


def test_symbol_and_reference_name_the_same_parameters():
    sym = model.symbol(TINY)
    assert [n for n in sym.list_arguments()
            if n not in ("data", "softmax_label")] == list(
                ref.param_shapes(TINY))
    assert model.param_shapes(TINY) == ref.param_shapes(TINY)


@pytest.mark.parametrize("tokens", [32, 48])
def test_module_is_the_reference_in_float32(tokens):
    """Loss, softmax outputs and every leaf's gradient, at a length the
    window (12) covers several times. Matrices at 0.2, ten times the
    configuration's, so that attention's scores and the routing differ
    from token to token."""
    mod, w0, batches = _module(tokens=tokens, recompute=True)
    ids, labels = batches[0]
    mod.forward_backward(_batch(ids, labels))
    probs = mod.get_outputs()[0].asnumpy()
    grads = {n: mod._exec.grad_dict[n].asnumpy() for n in mod._param_names}
    (loss, want_probs), want = jax.value_and_grad(ref.loss_fn, has_aux=True)(
        {n: jnp.asarray(v) for n, v in w0.items()}, jnp.asarray(ids),
        jnp.asarray(labels), TINY)
    assert np.abs(np.log(probs) - np.log(np.asarray(want_probs))).max() < 1e-4
    labels = labels.reshape(-1).astype(int)
    got_loss = -np.log(probs[np.arange(labels.size), labels]).mean()
    assert got_loss == pytest.approx(float(loss), rel=1e-5)
    gaps = _leaf_gaps(grads, {n: np.asarray(g) for n, g in want.items()})
    assert max(gaps.values()) < 1e-3, max(gaps, key=gaps.get)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_fit_steps_of_adam_follow_the_reference(dtype):
    """`Module.fit` under Adam on the fused step with every layer
    recomputed: parameters and first moments after three steps are the
    reference's (written-out Adam, the same policy); weights and moments
    stay float32."""
    mod, w0, batches = _module(dtype=dtype, recompute=True, std=0.02)
    descs = ([mx.io.DataDesc("data", (2, 32), np.int32)],
             [mx.io.DataDesc("softmax_label", (2, 32), np.float32)])

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

    mod.fit(Three(), eval_metric="ce", optimizer="adam",
            optimizer_params=dict(ADAM, wd=0.0, rescale_grad=1.0),
            num_epoch=1)
    assert mod._fused_plan not in (None, False)
    policy = "f32" if dtype == "float32" else "bf16"
    want = ref.follow(TINY, ADAM, {n: jnp.asarray(v) for n, v in w0.items()},
                      [(jnp.asarray(i), jnp.asarray(l))
                       for i, l in batches[:3]], policy)
    got = {n: v.asnumpy() for n, v in mod.get_params()[0].items()}
    moved = lambda p: {n: np.asarray(p[n], "f") - w0[n] for n in w0}
    tol = 1e-3 if dtype == "float32" else 0.05
    gaps = _leaf_gaps(moved(got), moved(want["params"]))
    assert np.median(list(gaps.values())) < tol, gaps
    means = {n: mod._updater.states[i][0].asnumpy()
             for i, n in enumerate(mod._param_names)}
    gaps = _leaf_gaps(means, {n: np.asarray(v)
                              for n, v in want["mean"].items()})
    assert np.median(list(gaps.values())) < tol, gaps
    assert all(v.dtype == np.float32 for v in got.values())
    assert all(m.dtype == np.float32 for m in means.values())


def test_rows_past_the_groups_never_reach_the_result(monkeypatch):
    """The chip's grouped kernels leave the rows past the held experts'
    groups unwritten: filled here with NaN, in the products' results
    forward and in their gradients backward, the layer's output and all
    five gradients stay the dense layer's."""
    grouped = moe_ops.grouped

    @jax.custom_vjp
    def dirty(rows, weights, sizes):
        return grouped(rows, weights, sizes)

    def spoil(out, sizes):
        past = jnp.arange(out.shape[0])[:, None] >= jnp.sum(sizes)
        return jnp.where(past, jnp.nan, out)

    def dirty_fwd(rows, weights, sizes):
        return spoil(grouped(rows, weights, sizes), sizes), \
            (rows, weights, sizes)

    def dirty_bwd(res, g):
        rows, weights, sizes = res
        _, vjp = jax.vjp(lambda r, w: grouped(r, w, sizes), rows, weights)
        g_rows, g_weights = vjp(g)
        return spoil(g_rows, sizes), g_weights, None

    dirty.defvjp(dirty_fwd, dirty_bwd)
    monkeypatch.setattr(moe_ops, "grouped", dirty)
    args = _held(_layer_inputs(), 2, 2)
    w = jnp.asarray(np.random.RandomState(9).randn(2, 24, 16), jnp.float32)
    op = lambda *a: moe_ops.moe(*a, first_held=2, top_k=3)
    dense = lambda *a: _dense_layer(*a, 2, 3)
    np.testing.assert_allclose(op(*args), dense(*args), rtol=1e-5,
                               atol=1e-5)
    for got, want in zip(*[jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                                     argnums=range(5))(*args)
                           for fn in (op, dense)]):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
