"""ResNet-50 v1.5 (He et al. 2015, arXiv:1512.03385, Table 1; stride on the
3x3 convolution of a stage's first block), forward and loss in plain float32.

Departures from the paper, each one the program's own and part of what the
cell states: ``SAME`` padding in the TensorFlow sense on strided convolutions
(so 2+3 on the 7x7 stem, 0+1 on a strided 3x3) instead of symmetric padding;
label smoothing 0.1 in the loss; batch statistics in training mode.
Activations are kept per block only (``jax.checkpoint``), so that batch 256 at
224x224 in float32 fits one chip: batch normalisation ties the rows together,
so the batch cannot be cut into blocks of rows.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.numerics import Numerics

BN_EPS = 1e-5


def _blocks(model):
    for stage, n in enumerate(model["stage_sizes"]):
        for b in range(n):
            yield f"stage{stage}_block{b}", model["width"] * 2 ** stage, (
                2 if stage > 0 and b == 0 else 1)


def _bn_width(model):
    out, cin = {"bn_stem": model["width"]}, model["width"]
    for name, f, stride in _blocks(model):
        out.update({f"{name}/bn1": f, f"{name}/bn2": f, f"{name}/bn3": 4 * f})
        if cin != 4 * f or stride != 1:
            out[f"{name}/bn_proj"] = 4 * f
        cin = 4 * f
    return out


def param_spec(model) -> dict:
    """Every leaf drawn nonzero (scales about 1, biases about 0), so that
    every path of the backward pass carries a gradient at the compared steps:
    the example's zero-initialised last scale of a block would leave 48
    kernels with none."""
    spec = {}

    def conv(path, kh, cin, cout):
        spec[f"{path}/kernel"] = ((kh, kh, cin, cout), 0.0,
                                  math.sqrt(2.0 / (kh * kh * cin)))

    w = model["width"]
    conv("conv_stem", 7, 3, w)
    cin = w
    for name, f, stride in _blocks(model):
        conv(f"{name}/conv1", 1, cin, f)
        conv(f"{name}/conv2", 3, f, f)
        conv(f"{name}/conv3", 1, f, 4 * f)
        if cin != 4 * f or stride != 1:
            conv(f"{name}/conv_proj", 1, cin, 4 * f)
        cin = 4 * f
    for bn, c in _bn_width(model).items():
        # a block's last scale is kept small: the example starts it at zero,
        # and at 0.5 the 16 residual sums make the backward pass so sensitive
        # that bfloat16 and fp8 both read a gap of 0.2 in the first stage's
        # leaves (PERF.md, PR 24); at 0.1 every kernel still has a gradient
        mean, std = (0.1, 0.02) if bn.endswith("bn3") else (1.0, 0.1)
        spec[f"{bn}/scale"] = ((c,), mean, std)
        spec[f"{bn}/bias"] = ((c,), 0.0, 0.1)
    spec["head/kernel"] = ((cin, model["num_classes"]), 0.0,
                           math.sqrt(1.0 / cin))
    spec["head/bias"] = ((model["num_classes"],), 0.0, 0.01)
    return spec


def state_spec(model) -> dict:
    """Running statistics of every batch normalisation: the program carries
    them, the loss in training mode does not read them."""
    spec = {}
    for bn, c in _bn_width(model).items():
        spec[f"batch_stats/{bn}/mean"] = ((c,), 0.0, 0.0)
        spec[f"batch_stats/{bn}/var"] = ((c,), 1.0, 0.0)
    return spec


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _block(num: Numerics, stride: int, p, x):
    y = jax.nn.relu(_bn(num.conv(x, p["conv1"]["kernel"], 1, "SAME"), p["bn1"]))
    y = jax.nn.relu(_bn(num.conv(y, p["conv2"]["kernel"], stride, "SAME"),
                        p["bn2"]))
    y = _bn(num.conv(y, p["conv3"]["kernel"], 1, "SAME"), p["bn3"])
    if "conv_proj" in p:
        x = _bn(num.conv(x, p["conv_proj"]["kernel"], stride, "SAME"),
                p["bn_proj"])
    return jax.nn.relu(x + y)


def logits(model, params, images, num: Numerics):
    x = images.astype(jnp.float32)
    x = jax.nn.relu(_bn(num.conv(x, params["conv_stem"]["kernel"], 2, "SAME"),
                        params["bn_stem"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    for name, _, stride in _blocks(model):
        x = jax.checkpoint(lambda p, x, s=stride: _block(num, s, p, x))(
            params[name], x)
    x = jnp.mean(x, axis=(1, 2))
    return num.einsum("bc,cn->bn", x, params["head"]["kernel"]) + params[
        "head"]["bias"]


def loss(model, job, params, batch, num: Numerics = Numerics()):
    """Mean smoothed cross-entropy over the batch."""
    lg = logits(model, params, batch["image"], num)
    n = model["num_classes"]
    eps = job["label_smoothing"]
    soft = jax.nn.one_hot(batch["label"], n) * (1.0 - eps) + eps / n
    per_row = -jnp.sum(soft * jax.nn.log_softmax(lg), axis=-1)
    return jnp.mean(per_row)
