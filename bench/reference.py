"""The plain reference for the conv-network configurations, and the check.

Written from the configuration file alone: canonical NHWC float32, one
``lax.conv_general_dilated`` per layer (depthwise layers as grouped
convolutions), the boundary adapter the file describes, and the residual
adds of its ``skip_edges``.  It imports nothing of the program and takes
nothing the program made: the weights and inputs come from this
directory's own seeded generators (``init_weights``, ``make_inputs``), and
the benchmark hands the same arrays to the program.

The number compared is, per request, the largest absolute gap between the
served output and the reference over the reference's largest magnitude;
a run reports the largest over the requests it checks.  The reference runs
at ``highest`` precision.  Its control is the same reference computed at
bf16_3x (each float32 operand split into a bfloat16 high and low part,
the low-by-low product dropped, each pass exact in float32), which is
what the TPU's ``high`` precision computes: the step below the
configuration's ``float32, highest``, the one a later change would be
tempted to take.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from work import in_channels, in_hw

PRECISIONS = ("highest", "bf16_3x")


def _key(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any whole-number seed: the low and high 32 bits are
    both folded in, so seeds above 2**32 do not collide."""
    seed %= 2 ** 64
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


def weight_shape(layer: Dict):
    """(R, S, M) for a depthwise layer, else (R, S, C, M)."""
    if layer.get("depthwise"):
        return (layer["R"], layer["S"], layer["M"])
    return (layer["R"], layer["S"], layer["C"], layer["M"])


def init_weights(layers: Sequence[Dict], seed: int) -> List[jax.Array]:
    """Seeded float32 weights for every layer, scaled normals made on the
    device in one jitted call."""
    shapes = [weight_shape(layer) for layer in layers]
    fan_in = [layer["R"] * layer["S"] * (1 if layer.get("depthwise")
                                        else layer["C"]) for layer in layers]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return [jax.random.normal(k, s, jnp.float32) / np.sqrt(f)
                for k, s, f in zip(keys, shapes, fan_in)]

    return make(_key(seed, 0))


def input_shape(layers: Sequence[Dict]):
    """Per-request (H, W, C): the canvas the first layer reads."""
    h, w = in_hw(layers[0])
    return (h, w, in_channels(layers[0]))


def make_inputs(layers: Sequence[Dict], seed: int, n: int,
                image=None) -> np.ndarray:
    """``n`` seeded request inputs, made on the device, returned to the host
    as the requests a client would send.  With ``image`` (H, W, C), each is
    a seeded image of that size zero-padded to the first layer's canvas
    (SAME padding, the larger half after); else the canvas is all data."""
    canvas = input_shape(layers)
    h, w, c = canvas if image is None else tuple(image)
    if c != canvas[2] or h > canvas[0] or w > canvas[1]:
        raise ValueError(f"image {image} does not fit the canvas {canvas}")
    top, left = (canvas[0] - h) // 2, (canvas[1] - w) // 2
    pads = ((0, 0), (top, canvas[0] - h - top), (left, canvas[1] - w - left),
            (0, 0))

    @jax.jit
    def make(key):
        return jnp.pad(jax.random.normal(key, (n, h, w, c), jnp.float32), pads)

    return np.asarray(make(_key(seed, 1)))


def _adapt(a: jax.Array, h: int, w: int, c: int) -> jax.Array:
    """The configuration's boundary adapter, in canonical NHWC."""
    n, ha, wa, ca = a.shape
    if ha > h:
        a = a[:, ::ha // h][:, :h]
    elif ha < h:
        lo = (h - ha) // 2
        a = jnp.pad(a, ((0, 0), (lo, h - ha - lo), (0, 0), (0, 0)))
    if wa > w:
        a = a[:, :, ::wa // w][:, :, :w]
    elif wa < w:
        lo = (w - wa) // 2
        a = jnp.pad(a, ((0, 0), (0, 0), (lo, w - wa - lo), (0, 0)))
    if ca > c:
        a = a[..., :c]
    elif ca < c:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, c - ca)))
    return a


def _conv(a, w, stride, groups, precision):
    dn = ("NHWC", "HWIO", "NHWC")

    def conv(x, k):
        return lax.conv_general_dilated(
            x, k, (stride, stride), "VALID", dimension_numbers=dn,
            feature_group_count=groups, precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    if precision == "highest":
        return conv(a, w)

    def split(x):
        # reduce_precision rounds to bfloat16's 8-bit mantissa and stays
        # float32: a cast pair would be folded away by XLA on the TPU,
        # which keeps the excess precision
        hi = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
        return hi, lo

    (a_hi, a_lo), (w_hi, w_lo) = split(a), split(w)
    return conv(a_hi, w_hi) + conv(a_hi, w_lo) + conv(a_lo, w_hi)


def forward(layers: Sequence[Dict], skip_edges, x: jax.Array,
            weights: Sequence[jax.Array], precision: str = "highest"
            ) -> jax.Array:
    """The network's output (N, P, Q, M) for inputs ``x`` (N, H, W, C)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    outs: List[jax.Array] = []
    cur = x.astype(jnp.float32)
    for i, (layer, w) in enumerate(zip(layers, weights)):
        h, wd = in_hw(layer)
        a = _adapt(cur, h, wd, in_channels(layer))
        if layer.get("depthwise"):
            k = w.reshape(layer["R"], layer["S"], 1, layer["M"])
            groups = layer["M"]
        else:
            k, groups = w, 1
        y = _conv(a, k, layer["stride"], groups, precision)
        for src, dst in skip_edges:
            if dst == i:
                y = y + _adapt(outs[src], layer["P"], layer["Q"], layer["M"])
        outs.append(y)
        cur = y
    return cur


@functools.lru_cache(maxsize=8)
def _jitted(layers_key, skips_key, precision):
    layers = [dict(items) for items in layers_key]
    return jax.jit(lambda x, w: forward(layers, skips_key, x, w, precision))


def reference_outputs(layers: Sequence[Dict], skip_edges, inputs: np.ndarray,
                      weights: Sequence[jax.Array], block: int,
                      precision: str = "highest") -> np.ndarray:
    """The reference over ``inputs`` in blocks of ``block`` rows (the last
    block padded), so one compiled program serves every block."""
    fn = _jitted(tuple(tuple(sorted(layer.items())) for layer in layers),
                 tuple(tuple(e) for e in skip_edges), precision)
    outs = []
    for s in range(0, len(inputs), block):
        xb = inputs[s:s + block]
        n = len(xb)
        if n < block:
            xb = np.concatenate([xb, np.zeros((block - n,) + xb.shape[1:],
                                              xb.dtype)])
        outs.append(np.asarray(fn(jnp.asarray(xb), list(weights)))[:n])
    return np.concatenate(outs)


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest absolute gap over the reference's largest magnitude."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))
