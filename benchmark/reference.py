"""The plain reference: a convolutional classifier and its SGD-momentum
training step in straightforward jax.numpy. It imports nothing of the
program and is given nothing the program made: the architecture is a list
of layer records written from the paper (``benchmark/refs/<name>.py``), the
weights and the batches come from the benchmark's own seeded generators.

A layer record is a dict with an ``op``:

  conv     name, out, kernel, stride, pad, bias
  bn       name                      (batch statistics, eps 1e-5)
  relu | flatten | gap
  maxpool  kernel, stride, pad
  dense    name, out                 (always with bias)
  dropout  p                         (the reference refuses p > 0: it cannot
                                      draw the program's mask)
  residual body=[...], shortcut=[...]   relu(body(x) + shortcut(x))

Precision policies (``policy``):

  bf16  parameters, activations and gradients held in bfloat16; products
        accumulate in float32 inside a convolution; BatchNorm moments (one
        pass) and backward pass, softmax and the optimizer's arithmetic in
        float32, BatchNorm's scale and shift applied in bfloat16. What the
        two configurations state.
  fp8   the control: as bf16, but every convolution and dense layer sees
        its operands rounded to float8_e4m3 (scaled to the tensor's largest
        magnitude) and hands back a cotangent rounded to float8_e5m2: the
        nearest precision below bfloat16, the step a later PR would be
        tempted by.
  f32   float32 at ``highest``: for the CPU tests, which hold the layer list
        against the program's symbol tightly.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BN_EPS = 1e-5


# -- layer records ------------------------------------------------------------

def conv(name, out, kernel, stride=1, pad=0, bias=True):
    return {"op": "conv", "name": name, "out": out, "kernel": kernel,
            "stride": stride, "pad": pad, "bias": bias}


def bn(name):
    return {"op": "bn", "name": name}


def dense(name, out):
    return {"op": "dense", "name": name, "out": out}


def maxpool(kernel, stride, pad=0):
    return {"op": "maxpool", "kernel": kernel, "stride": stride, "pad": pad}


def residual(body, shortcut):
    return {"op": "residual", "body": body, "shortcut": shortcut}


RELU = {"op": "relu"}
FLATTEN = {"op": "flatten"}
GAP = {"op": "gap"}


def dropout(p):
    return {"op": "dropout", "p": p}


# -- shapes: parameters, and the work of each layer ---------------------------

def _hw(shape, layout):
    return (shape[1], shape[2]) if layout == "NHWC" else (shape[2], shape[3])


def _chan(shape, layout):
    return shape[3] if layout == "NHWC" else shape[1]


def _with(shape, layout, h, w, c):
    return (shape[0], h, w, c) if layout == "NHWC" else (shape[0], c, h, w)


def trace(layers, shape, layout):
    """Walk ``layers`` from the input ``shape``. Returns (records, out
    shape); a record is (layer, input shape, output shape, {parameter name:
    shape}) for every layer in execution order, residual branches inlined."""
    records = []
    for layer in layers:
        op = layer["op"]
        params = {}
        out = shape
        if op == "conv":
            h, w = _hw(shape, layout)
            k, s, p = layer["kernel"], layer["stride"], layer["pad"]
            cin = _chan(shape, layout)
            out = _with(shape, layout, (h + 2 * p - k) // s + 1,
                        (w + 2 * p - k) // s + 1, layer["out"])
            params[layer["name"] + "_weight"] = (
                (layer["out"], k, k, cin) if layout == "NHWC"
                else (layer["out"], cin, k, k))
            if layer["bias"]:
                params[layer["name"] + "_bias"] = (layer["out"],)
        elif op == "bn":
            c = _chan(shape, layout)
            params[layer["name"] + "_gamma"] = (c,)
            params[layer["name"] + "_beta"] = (c,)
        elif op == "maxpool":
            h, w = _hw(shape, layout)
            k, s, p = layer["kernel"], layer["stride"], layer["pad"]
            out = _with(shape, layout, (h + 2 * p - k) // s + 1,
                        (w + 2 * p - k) // s + 1, _chan(shape, layout))
        elif op == "gap":
            out = (shape[0], _chan(shape, layout))
        elif op == "flatten":
            out = (shape[0], int(np.prod(shape[1:])))
        elif op == "dense":
            params[layer["name"] + "_weight"] = (layer["out"], shape[1])
            params[layer["name"] + "_bias"] = (layer["out"],)
            out = (shape[0], layer["out"])
        elif op == "residual":
            body, out = trace(layer["body"], shape, layout)
            short, short_out = trace(layer["shortcut"], shape, layout)
            if short_out != out:
                raise ValueError("residual branches disagree: %s and %s"
                                 % (out, short_out))
            records += body + short
        elif op not in ("relu", "dropout"):
            raise ValueError("unknown layer op %r" % op)
        records.append((layer, shape, out, params))
        shape = out
    return records, shape


def param_shapes(layers, shape, layout):
    """{parameter name: shape} in execution order."""
    shapes = {}
    for _, _, _, params in trace(layers, shape, layout)[0]:
        shapes.update(params)
    return shapes


def bn_names(layers):
    """Names of the BatchNorm layers (the program keeps running statistics
    under ``<name>_running_mean`` / ``_running_var``)."""
    names = []
    for layer in layers:
        if layer["op"] == "bn":
            names.append(layer["name"])
        elif layer["op"] == "residual":
            names += bn_names(layer["body"]) + bn_names(layer["shortcut"])
    return names


# -- the seeded generators: weights and batches -------------------------------

def seed_key(seed, stream):
    """A PRNG key from any whole number (the driver's seeds pass 2**31) and
    a stream number, so weights and data never share draws."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x3FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 30) & 0x3FFFFFFF)
    return jax.random.fold_in(key, stream)


def make_params(shapes, seed, dtype, init):
    """Every parameter from the seed in ONE jitted call, on the default
    device, in ``dtype``: He-normal weights (fan-in), zero biases and betas,
    unit gammas. ``init["classifier_std"]``, where given, is the standard
    deviation of the LAST weight in execution order (the classifier's)
    instead."""
    names = list(shapes)
    last = [n for n in names if n.endswith("_weight")][-1]
    classifier_std = init.get("classifier_std")

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            shape = shapes[name]
            if name.endswith("_weight"):
                std = np.sqrt(2.0 / int(np.prod(shape[1:])))
                if name == last and classifier_std is not None:
                    std = classifier_std
                w = jax.random.normal(k, shape, jnp.float32)
                out[name] = (w * np.float32(std)).astype(dtype)
            elif name.endswith("_gamma"):
                out[name] = jnp.ones(shape, dtype)
            else:
                out[name] = jnp.zeros(shape, dtype)
        return out

    return draw(seed_key(seed, 1))


def make_pool(seed, batches, batch, image, classes, layout, dtype):
    """``batches`` distinct batches from the seed in one jitted call: each
    class a coarse 4x4 colour pattern under noise, so that SGD has
    something to learn and the loss stays finite over a long window.
    Returns (images [batches, batch, ...] in ``dtype``, labels [batches,
    batch] float32, as the program's label input takes them)."""
    @jax.jit
    def draw(key):
        k_pat, k_lab, k_noise = jax.random.split(key, 3)
        label = jax.random.randint(k_lab, (batches, batch), 0, classes,
                                   jnp.int32)
        pattern = jax.random.uniform(k_pat, (classes, 4, 4, 3), jnp.float32)
        x = pattern[label]                       # [batches, batch, 4, 4, 3]
        x = jnp.repeat(jnp.repeat(x, image // 4, 2), image // 4, 3)
        x = x + 0.25 * jax.random.uniform(
            k_noise, (batches, batch, image, image, 3), jnp.float32)
        if layout == "NCHW":
            x = x.transpose(0, 1, 4, 2, 3)
        return x.astype(dtype), label.astype(jnp.float32)

    return draw(seed_key(seed, 2))


# -- forward ------------------------------------------------------------------

def _fp8_round(x, dtype):
    """``x`` rounded to the float8 ``dtype`` at the tensor's own scale."""
    x32 = x.astype(jnp.float32)
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-30) / top
    return ((x32 / scale).astype(dtype).astype(jnp.float32)
            * scale).astype(x.dtype)


@jax.custom_vjp
def _fp8_operand(x):
    return _fp8_round(x, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda x: (_fp8_operand(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(y):
    return y


_fp8_cotangent.defvjp(lambda y: (y, None),
                      lambda _, g: (_fp8_round(g, jnp.float8_e5m2),))


def _matmul_like(policy, fn, x, w):
    if policy == "fp8":
        return _fp8_cotangent(fn(_fp8_operand(x), _fp8_operand(w)))
    return fn(x, w)


def _precision(policy):
    return lax.Precision.HIGHEST if policy == "f32" else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def batch_norm(x, gamma, beta, channel):
    """BatchNorm on the batch's own statistics, in the arithmetic the
    configurations state: the moments in one pass in float32 (E[x^2] -
    E[x]^2, never below 0), the backward pass in float32, the normalization
    applied as one scale and shift in the activation's own type. (Against
    the two-pass variance the first step's outputs of ResNet-50 part by 1.3
    to 4.1 %, against this one by 0.3 %: PERF.md section 6, PR 23.)"""
    return _bn_forward(x, gamma, beta, channel)[0]


def _bn_forward(x, gamma, beta, channel):
    axes = tuple(a for a in range(x.ndim) if a != channel)
    shape = [1] * x.ndim
    shape[channel] = x.shape[channel]
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axes)
    var = jnp.maximum(jnp.mean(x32 * x32, axes) - mean * mean, 0.0)
    inv = lax.rsqrt(var + BN_EPS)
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean * scale
    out = x * scale.astype(x.dtype).reshape(shape) \
        + shift.astype(x.dtype).reshape(shape)
    return out, (x, gamma, mean, inv)


def _bn_backward(channel, saved, dy):
    """The textbook backward pass, in float32."""
    x, gamma, mean, inv = saved
    axes = tuple(a for a in range(x.ndim) if a != channel)
    shape = [1] * x.ndim
    shape[channel] = x.shape[channel]
    n = x.size // x.shape[channel]
    xhat = (x.astype(jnp.float32) - mean.reshape(shape)) * inv.reshape(shape)
    dy32 = dy.astype(jnp.float32)
    dbeta = jnp.sum(dy32, axes)
    dgamma = jnp.sum(dy32 * xhat, axes)
    dx = (gamma.astype(jnp.float32) * inv).reshape(shape) * (
        dy32 - dbeta.reshape(shape) / n - xhat * dgamma.reshape(shape) / n)
    return dx.astype(x.dtype), dgamma.astype(gamma.dtype), \
        dbeta.astype(gamma.dtype)


batch_norm.defvjp(lambda x, g, b, c: _bn_forward(x, g, b, c), _bn_backward)


def forward(layers, params, x, layout, policy):
    """Logits of the training-mode forward pass (BatchNorm on the batch's
    own statistics)."""
    channel = 3 if layout == "NHWC" else 1
    spatial = (1, 2) if layout == "NHWC" else (2, 3)
    dims = ("NHWC", "OHWI", "NHWC") if layout == "NHWC" \
        else ("NCHW", "OIHW", "NCHW")
    prec = _precision(policy)

    def per_channel(v, ndim):
        shape = [1] * ndim
        shape[channel] = v.shape[0]
        return v.reshape(shape)

    for layer in layers:
        op = layer["op"]
        if op == "conv":
            s, p = layer["stride"], layer["pad"]
            x = _matmul_like(
                policy,
                lambda a, w, s=s, p=p: lax.conv_general_dilated(
                    a, w, (s, s), [(p, p), (p, p)], dimension_numbers=dims,
                    precision=prec),
                x, params[layer["name"] + "_weight"])
            if layer["bias"]:
                x = x + per_channel(params[layer["name"] + "_bias"], x.ndim)
        elif op == "bn":
            x = batch_norm(x, params[layer["name"] + "_gamma"],
                           params[layer["name"] + "_beta"], channel)
        elif op == "relu":
            x = jnp.maximum(x, 0)
        elif op == "maxpool":
            k, s, p = layer["kernel"], layer["stride"], layer["pad"]
            window, strides, pads = [1] * 4, [1] * 4, [(0, 0)] * 4
            for a in spatial:
                window[a], strides[a], pads[a] = k, s, (p, p)
            x = lax.reduce_window(x, np.array(-np.inf, x.dtype), lax.max,
                                  window, strides, pads)
        elif op == "gap":
            x = jnp.mean(x.astype(jnp.float32), spatial).astype(x.dtype)
        elif op == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif op == "dense":
            x = _matmul_like(
                policy,
                lambda a, w: jnp.matmul(a, w.T, precision=prec),
                x, params[layer["name"] + "_weight"])
            x = x + params[layer["name"] + "_bias"]
        elif op == "dropout":
            if layer["p"] > 0:
                raise ValueError(
                    "the reference cannot draw the program's dropout mask: "
                    "run the configuration with dropout 0")
        elif op == "residual":
            x = jnp.maximum(
                forward(layer["body"], params, x, layout, policy)
                + forward(layer["shortcut"], params, x, layout, policy), 0)
        else:
            raise ValueError("unknown layer op %r" % op)
    return x


def loss_fn(layers, params, x, label, layout, policy):
    """(mean cross-entropy of the batch, the softmax outputs), in float32.
    The lower-precision
    policies compute in bfloat16 whatever type the parameters are kept in."""
    if policy in ("bf16", "fp8"):
        params = {n: w.astype(jnp.bfloat16) for n, w in params.items()}
        x = x.astype(jnp.bfloat16)
    logits = forward(layers, params, x, layout, policy).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], 1)
    return -jnp.mean(picked), jnp.exp(logp)


#: the nearest precision below the one a configuration states: the control
POLICY = {"float32": "f32", "bfloat16": "bf16"}
BELOW = {"f32": "bf16", "bf16": "fp8"}


# -- SGD with momentum, as the configurations state it ------------------------

def decays(name):
    """Weight decay falls on weights and BatchNorm scales, not on biases or
    shifts (MXNet's rule, which both papers' recipes follow)."""
    return name.endswith("_weight") or name.endswith("_gamma")


def make_step(layers, layout, policy, lr, momentum, wd):
    """One jitted training step: (params, moms, x, label) -> (params, moms,
    loss, softmax outputs). The optimizer works in float32 and stores in the
    parameter's type."""
    grad = jax.value_and_grad(
        functools.partial(loss_fn, layers, layout=layout, policy=policy),
        has_aux=True)

    @jax.jit
    def step(params, moms, x, label):
        (loss, probs), grads = grad(params, x=x, label=label)
        new_p, new_m = {}, {}
        for name, w in params.items():
            g = grads[name].astype(jnp.float32)
            if decays(name):
                g = g + np.float32(wd) * w.astype(jnp.float32)
            m = np.float32(momentum) * moms[name].astype(jnp.float32) \
                - np.float32(lr) * g
            new_m[name] = m.astype(moms[name].dtype)
            new_p[name] = (w.astype(jnp.float32) + m).astype(w.dtype)
        return new_p, new_m, loss, probs

    return step


def follow(layers, layout, policy, optimizer, params, batches):
    """Drive the reference through ``batches`` [(x, label), ...] from
    ``params``. Returns what `compare.py` holds the program to: each step's
    loss, the first step's momentum (from which the first gradient is worked
    out, as it is from the program's), and the parameters at the end."""
    step = make_step(layers, layout, policy, optimizer["learning_rate"],
                     optimizer["momentum"], optimizer["wd"])
    moms = {n: jnp.zeros_like(w) for n, w in params.items()}
    losses, first_mom, first_probs = [], None, None
    for i, (x, label) in enumerate(batches):
        params, moms, loss, probs = step(params, moms, x, label)
        losses.append(loss)
        if i == 0:
            first_mom, first_probs = moms, probs
    return {"losses": [float(v) for v in losses], "first_mom": first_mom,
            "first_probs": first_probs, "params": params}
