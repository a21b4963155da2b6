"""Plain reference math, independent of the system under test.

Straightforward ``jax.numpy`` in float32, NCHW, no kernels, no swapping, no
batching tricks.  It imports nothing of ``repro`` and takes nothing the
program made: the weights and inputs both sides use come from
:func:`make_params` and the data makers here, drawn from the run's seed.

Three precisions: ``"highest"`` (every conv and dot at
``lax.Precision.HIGHEST``, which is what ``jax.default_matmul_precision(
"float32")`` asks of the program); ``"high"``, the control: each operand
split into two bfloat16 pieces and multiplied in three passes (hi*hi +
hi*lo + lo*hi), forward and backward, as the TPU's ``HIGH`` precision
computes, written out so that it runs on any backend; and ``"bfloat16"``:
each operand rounded to bfloat16 once, products summed in float32, as the
TPU's default precision computes.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PRECISIONS = ("highest", "high", "bfloat16")

Params = Dict[str, Dict[str, jax.Array]]


def prng_key(seed: int) -> jax.Array:
    """A key that keeps all the bits of a seed of any size (``PRNGKey``
    alone keeps only the low 32)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    high = seed >> 32
    while high:
        key = jax.random.fold_in(key, high & 0xFFFFFFFF)
        high >>= 32
    return key


# ---------------------------------------------------------------------------
# bilinear ops at a stated precision
# ---------------------------------------------------------------------------

def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _three_pass(op: Callable) -> Callable:
    """``op`` (bilinear in its two arguments) in three bfloat16 passes,
    with a backward pass that is three passes too."""

    def passes(a, b):
        ah, al = _split(a)
        bh, bl = _split(b)
        return op(ah, bh) + (op(ah, bl) + op(al, bh))

    @jax.custom_vjp
    def f(a, b):
        return passes(a, b)

    def fwd(a, b):
        return passes(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        # op is linear in each argument, so its transposes are bilinear in
        # (cotangent, other argument) and take the same three passes
        da = _transpose_passes(
            lambda gg, bb: jax.vjp(lambda aa: op(aa, bb), a)[1](gg)[0], g, b)
        db = _transpose_passes(
            lambda gg, aa: jax.vjp(lambda bb: op(aa, bb), b)[1](gg)[0], g, a)
        return da, db

    f.defvjp(fwd, bwd)
    return f


def _one_pass(op: Callable) -> Callable:
    """``op`` on operands rounded to bfloat16, forward and backward."""

    def rounded(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    @jax.custom_vjp
    def f(a, b):
        return op(rounded(a), rounded(b))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        ra, rb, rg = rounded(a), rounded(b), rounded(g)
        da = jax.vjp(lambda aa: op(aa, rb), ra)[1](rg)[0]
        db = jax.vjp(lambda bb: op(ra, bb), rb)[1](rg)[0]
        return da, db

    f.defvjp(fwd, bwd)
    return f


def _transpose_passes(t: Callable, g, other):
    gh, gl = _split(g)
    oh, ol = _split(other)
    return t(gh, oh) + (t(gh, ol) + t(gl, oh))


def _conv_op(stride: int):
    def op(x, w):
        return lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)
    return op


def _dot_op(a, b):
    return jnp.dot(a, b, precision=HIGHEST)


class Ops:
    """conv2d / dense at one precision."""

    def __init__(self, precision: str):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.precision = precision
        self._wrap = {"highest": lambda op: op, "high": _three_pass,
                      "bfloat16": _one_pass}[precision]
        self._convs: Dict[int, Callable] = {}
        self._dot = self._wrap(_dot_op)

    def conv(self, x, w, b, stride: int):
        f = self._convs.get(stride)
        if f is None:
            f = self._wrap(_conv_op(stride))
            self._convs[stride] = f
        return f(x, w) + b[None, :, None, None]

    def dense(self, x, w, b):
        return self._dot(x, w) + b


def maxpool(x, k: int):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, k, k),
                             (1, 1, k, k), "VALID")


def relu(x):
    # jax.nn.relu has derivative 0 at 0, as a backward from the output has
    return jax.nn.relu(x)


def cross_entropy(logits, onehot):
    """Mean softmax cross-entropy over the rows."""
    return -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits, axis=-1),
                             axis=-1))


# ---------------------------------------------------------------------------
# weights and inputs from the seed
# ---------------------------------------------------------------------------

def param_shapes(layers: List[dict]) -> Dict[str, Dict[str, Tuple[int, ...]]]:
    """``{layer: {"w": shape, "b": shape}}`` for a layer list of the kind
    the configurations' references give (``conv`` and ``dense`` entries)."""
    out = {}
    for l in layers:
        if l["kind"] == "conv":
            out[l["name"]] = {"w": (l["cout"], l["cin"], l["k"], l["k"]),
                              "b": (l["cout"],)}
        elif l["kind"] == "dense":
            out[l["name"]] = {"w": (l["cin"], l["cout"]), "b": (l["cout"],)}
    return out


def make_params(shapes: Dict[str, Dict[str, Tuple[int, ...]]], seed: int,
                gains: Optional[Dict[str, float]] = None,
                bias_scale: float = 0.05) -> Params:
    """He-normal weights and small normal biases, in one jitted call;
    ``gains`` scales the weights and bias of the layers it names."""
    names = sorted(shapes)
    gains = gains or {}

    def build(key):
        out = {}
        for i, name in enumerate(names):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            ws, bs = shapes[name]["w"], shapes[name]["b"]
            fan_in = math.prod(ws[1:]) if len(ws) == 4 else ws[0]
            g = gains.get(name, 1.0)
            out[name] = {
                "w": jax.random.normal(kw, ws, jnp.float32)
                * (g * math.sqrt(2.0 / fan_in)),
                "b": jax.random.normal(kb, bs, jnp.float32)
                * (g * bias_scale)}
        return out

    return jax.jit(build)(prng_key(seed))


def make_images(seed: int, stream: int, shape: Tuple[int, ...],
                classes: int):
    """``(x, onehot)`` with leading dims ``shape[:-3]``, in one jitted call;
    ``stream`` separates the draws one seed makes for different uses."""
    lead, img = tuple(shape[:-3]), tuple(shape[-3:])

    def build(key):
        kx, ky = jax.random.split(jax.random.fold_in(key, stream))
        x = jax.random.normal(kx, lead + img, jnp.float32)
        idx = jax.random.randint(ky, lead, 0, classes)
        return x, jax.nn.one_hot(idx, classes, dtype=jnp.float32)

    return jax.jit(build)(prng_key(seed))


# ---------------------------------------------------------------------------
# optimizers as the configurations state them
# ---------------------------------------------------------------------------

def momentum_sgd(params: Params, velocity: Params, grads: Params,
                 lr: float, momentum: float) -> Tuple[Params, Params]:
    """Heavy-ball SGD: ``v = momentum * v + g``, ``p = p - lr * v``."""
    v = jax.tree_util.tree_map(lambda a, g: momentum * a + g, velocity, grads)
    p = jax.tree_util.tree_map(lambda a, b: a - lr * b, params, v)
    return p, v


CBLOCK = 256          # elements per int8 block, one float32 scale each
V_LOG_FLOOR = 1e-16   # v is kept as 0.5*log(v + floor)


def _quantize(flat):
    n = flat.shape[0]
    nb = -(-n // CBLOCK)
    blocks = jnp.pad(flat, (0, nb * CBLOCK - n)).reshape(nb, CBLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q, scale, n):
    return (q.astype(jnp.float32) * scale).reshape(-1)[:n]


def _encode(mv, half):
    v = jnp.maximum(mv[half:], 0.0) + V_LOG_FLOOR
    return jnp.concatenate([mv[:half], 0.5 * jnp.log(v)])


def _decode(enc, half):
    v = jnp.exp(2.0 * enc[half:]) - V_LOG_FLOOR
    return jnp.concatenate([enc[:half], jnp.maximum(v, 0.0)])


def _adamw_layer(shapes, hyper, p, g, q, scale, residual, c1, c2):
    """One layer's step: decode the int8 copy, AdamW, re-quantize with the
    carried rounding error."""
    lr, b1, b2, eps, wd = hyper
    half = sum(math.prod(s) for s in shapes)
    mv = _decode(_dequantize(q, scale, 2 * half), half)
    new_p, ms, vs, off = [], [], [], 0
    for pk, gk, shape in zip(p, g, shapes):
        n = math.prod(shape)
        m = b1 * mv[off:off + n].reshape(shape) + (1 - b1) * gk
        v = b2 * mv[half + off:half + off + n].reshape(shape) \
            + (1 - b2) * gk * gk
        upd = (m / c1) / (jnp.sqrt(v / c2) + eps)
        new_p.append(pk - lr * (upd + wd * pk))
        ms.append(m.reshape(-1))
        vs.append(v.reshape(-1))
        off += n
    total = _encode(jnp.concatenate(ms + vs), half) + residual
    q2, s2 = _quantize(total)
    return new_p, q2, s2, total - _dequantize(q2, s2, 2 * half)


_adamw_layer_jit = jax.jit(_adamw_layer, static_argnums=(0, 1))


class Int8AdamW:
    """AdamW whose moments are kept between steps as int8 block-scaled
    copies with error feedback, as the training configuration states:
    per layer one flat vector ``m || v`` (leaves in the order ``w, b``),
    blocks of :data:`CBLOCK` elements with one absmax scale each, ``m``
    linear and ``v`` as ``0.5*log(v + 1e-16)``; each step decodes the copy,
    applies AdamW (bias-corrected, decoupled weight decay) and re-quantizes
    the new moments plus the carried rounding error."""

    LEAVES = ("w", "b")

    def __init__(self, params: Params, lr: float, b1: float, b2: float,
                 eps: float, weight_decay: float):
        self.hyper = (lr, b1, b2, eps, weight_decay)
        self.b1, self.b2 = b1, b2
        self.t = 0
        self.state = {}
        for name, entry in params.items():
            half = sum(int(entry[k].size) for k in self.LEAVES)
            enc = _encode(jnp.zeros((2 * half,), jnp.float32), half)
            q, scale = _quantize(enc)
            self.state[name] = (q, scale, enc - _dequantize(q, scale,
                                                           2 * half))

    def update(self, params: Params, grads: Params) -> Params:
        self.t += 1
        c1 = 1.0 - self.b1 ** self.t
        c2 = 1.0 - self.b2 ** self.t
        out = {}
        for name, entry in params.items():
            if name not in grads:
                out[name] = entry
                continue
            shapes = tuple(tuple(entry[k].shape) for k in self.LEAVES)
            q, scale, res = self.state[name]
            new_p, q, scale, res = _adamw_layer_jit(
                shapes, self.hyper, [entry[k] for k in self.LEAVES],
                [grads[name][k] for k in self.LEAVES], q, scale, res,
                jnp.float32(c1), jnp.float32(c2))
            self.state[name] = (q, scale, res)
            out[name] = dict(zip(self.LEAVES, new_p))
        return out
