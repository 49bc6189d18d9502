"""Plain ResNet (He et al., arXiv:1512.03385, Table 1) for ImageNet-sized
input: forward, loss and gradients in straightforward jax.numpy / lax.conv,
float32, NHWC. The yardstick for the `resnet` family; imports nothing of the
program. Weights come from `init(seed)` here; benchmarks/families/resnet.py
hands the same arrays to the program in the program's tree.

Training-mode BatchNorm over the rows it is given (biased variance,
eps 1e-5): a data-parallel cell calls this once per chip's share of the
batch, which is what per-replica BatchNorm computes. The stride of a
bottleneck sits on its 3x3 convolution, as in the program (and torchvision's
"v1.5"), not on the first 1x1 as in the paper: noted under `assumed` in the
configuration. Each block is wrapped in jax.checkpoint so that a 256-image
float32 backward pass fits one chip; that changes memory, not arithmetic.

`precision`: None = float32 (the reference); "fp8" runs every convolution
and the classifier as an fp8 step would (`reference/gpt2.fp8_op`: operands
rounded to float8_e4m3 forward, gradients to float8_e5m2 backward), the
control of a bfloat16 configuration.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.gpt2 import fp8_op

STAGES = {18: ("basic", (2, 2, 2, 2)), 34: ("basic", (3, 4, 6, 3)),
          50: ("bottleneck", (3, 4, 6, 3)), 101: ("bottleneck", (3, 4, 23, 3)),
          152: ("bottleneck", (3, 8, 36, 3))}
EPS = 1e-5


def blocks(cfg: dict):
    """[(name, n_in, planes, n_out, stride, kind)] in forward order."""
    kind, counts = STAGES[cfg["depth"]]
    expansion = 4 if kind == "bottleneck" else 1
    out, n_in = [], 64
    for s, (planes, stride) in enumerate(((64, 1), (128, 2), (256, 2),
                                          (512, 2))):
        for b in range(counts[s]):
            n_out = planes * expansion
            out.append((f"s{s}b{b}", n_in, planes, n_out,
                        stride if b == 0 else 1, kind))
            n_in = n_out
    return out


def layout(cfg: dict):
    """Every parameter in forward order: [(name, shape, kind)], kind one of
    conv (HWIO), gain, bias, fc_w (in, out), fc_b."""
    out = [("conv1.w", (7, 7, 3, 64), "conv"), ("bn1.g", (64,), "gain"),
           ("bn1.b", (64,), "bias")]

    def conv_bn(prefix, k, cin, cout):
        out.extend([(f"{prefix}.w", (k, k, cin, cout), "conv"),
                    (f"{prefix}.g", (cout,), "gain"),
                    (f"{prefix}.b", (cout,), "bias")])

    n_out = 64
    for name, n_in, planes, n_out, stride, kind in blocks(cfg):
        if kind == "bottleneck":
            conv_bn(f"{name}.c0", 1, n_in, planes)
            conv_bn(f"{name}.c1", 3, planes, planes)
            conv_bn(f"{name}.c2", 1, planes, n_out)
        else:
            conv_bn(f"{name}.c0", 3, n_in, planes)
            conv_bn(f"{name}.c1", 3, planes, n_out)
        if n_in != n_out or stride != 1:
            conv_bn(f"{name}.sc", 1, n_in, n_out)
    out.extend([("fc.w", (n_out, cfg["num_classes"]), "fc_w"),
                ("fc.b", (cfg["num_classes"],), "fc_b")])
    return out


def init(seed: int, cfg: dict) -> dict:
    """He et al. 2015 initialiser: conv ~ normal(0, sqrt(2 / (k*k*C_out))),
    BatchNorm gain 1 and bias 0, except that the last BatchNorm of every
    block starts at gain 0 (Goyal et al., arXiv:1706.02677 section 5.1, and
    the program's own models/resnet.py): each block starts as the identity,
    without which a 50-layer BatchNorm network's gradients at initialisation
    are chaotic (rounding in float32 alone moves single entries by percent);
    classifier ~ normal(0, 0.01) with bias 0.
    `seed` is a uint32 scalar, Python or traced: jit with it as an argument
    and one cached program serves every seed."""
    key = jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))
    # a bottleneck's middle BatchNorm is also called c1: not a last one
    skip = {f"{b[0]}.c1.g" for b in blocks(cfg) if b[5] == "bottleneck"}
    params = {}
    for i, (name, shape, kind) in enumerate(layout(cfg)):
        k = jax.random.fold_in(key, i)
        if kind == "conv":
            std = (2.0 / (shape[0] * shape[1] * shape[3])) ** 0.5
            params[name] = jax.random.normal(k, shape, jnp.float32) * std
        elif kind == "fc_w":
            params[name] = jax.random.normal(k, shape, jnp.float32) * 0.01
        elif kind == "gain":
            last = name.endswith((".c2.g", ".c1.g")) and name not in skip
            params[name] = (jnp.zeros if last else jnp.ones)(
                shape, jnp.float32)
        else:
            params[name] = jnp.zeros(shape, jnp.float32)
    return params


def _conv(x, w, stride, pad, precision):
    def op(x, w):
        return lax.conv_general_dilated(
            x, w, (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    return (fp8_op(op) if precision == "fp8" else op)(x, w)


def _bn(x, g, b):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean((x - mean) ** 2, axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + EPS) * g + b


def log_probs(params: dict, images, cfg: dict, precision=None):
    """(N, 224, 224, 3) float images -> (N, classes) log-probabilities,
    BatchNorm in training mode over these N rows."""
    p = params

    def cbr(x, prefix, stride, pad, relu=True):
        y = _bn(_conv(x, p[f"{prefix}.w"], stride, pad, precision),
                p[f"{prefix}.g"], p[f"{prefix}.b"])
        return jax.nn.relu(y) if relu else y

    x = _bn(_conv(images, p["conv1.w"], 2, 3, precision), p["bn1.g"],
            p["bn1.b"])
    x = jax.nn.relu(x)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    for name, n_in, planes, n_out, stride, kind in blocks(cfg):
        def block(x, name=name, n_in=n_in, n_out=n_out, stride=stride,
                  kind=kind):
            if kind == "bottleneck":
                y = cbr(x, f"{name}.c0", 1, 0)
                y = cbr(y, f"{name}.c1", stride, 1)
                y = cbr(y, f"{name}.c2", 1, 0, relu=False)
            else:
                y = cbr(x, f"{name}.c0", stride, 1)
                y = cbr(y, f"{name}.c1", 1, 1, relu=False)
            if n_in != n_out or stride != 1:
                x = cbr(x, f"{name}.sc", stride, 0, relu=False)
            return jax.nn.relu(x + y)

        x = jax.checkpoint(block)(x)
    x = jnp.mean(x, axis=(1, 2))
    matmul = fp8_op(jnp.matmul) if precision == "fp8" else jnp.matmul
    return jax.nn.log_softmax(matmul(x, p["fc.w"]) + p["fc.b"], axis=-1)


def loss(params, images, labels, cfg, precision=None):
    """Mean negative log-likelihood of 0-based integer labels."""
    lp = log_probs(params, images, cfg, precision)
    return -jnp.mean(jnp.take_along_axis(
        lp, labels[:, None].astype(jnp.int32), axis=-1))


def loss_and_grad_rows(params, images, labels, cfg, precision=None,
                       rows_per_block: int = 256):
    """Loss and gradient of the mean over equal blocks of rows, each block
    with its own BatchNorm statistics (one block = one replica's share)."""
    from benchmarks.reference.optim import loss_and_grad_in_blocks

    return loss_and_grad_in_blocks(
        lambda p, x, y: loss(p, x, y, cfg, precision), params, images,
        labels, rows_per_block)
