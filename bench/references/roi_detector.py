"""Plain reference of the RoI detector, and the weights both sides use.

What a configuration's reference gives the harness (the contract the
next configuration's reference keeps too), each from the configuration's
``detector`` dict:

* ``layers(detector)``: the detector layer by layer, as
  ``bench/harness/layers.py`` describes a layer: the FLOPs of
  ``mfu.step``, each kernel role's work and the receptive field that
  sets the generator's useful tiles are counted from it;
* ``init(key, detector)``: the weights, drawn from ``key`` on the device
  in one jitted call; the program runs with these very arrays;
* ``forward(params, frame, mask, passes="highest")``: the head map of
  one frame under a pixel mask, with no kernel, cache or batching;
* ``pixel_mask(grid, tile, shape)``: a camera's detector-tile grid as
  the (H, W, 1) pixel mask ``forward`` takes.

A conv stack of 3x3 SAME convolutions with ReLU (3 input channels, then
the widths of ``detector.channels``) and a 1x1 head of
``num_anchors * 5`` outputs (objectness and 4 box regressors per
anchor), in float32 ``jax.numpy`` with no kernel, cache or batching.
Under an RoI mask every layer's output and the head map are zeroed
outside the active tiles, so each layer sees the zero halo the packed
path reads; the first layer reads the whole frame, as the packed path's
haloed input windows do.  This module imports nothing of the program.

``passes`` selects the matmul precision: ``"highest"`` is float32 at
full precision (the configuration's); ``"bf16x3"`` is the control: each
operand split into bfloat16 high and low parts, rounded to nearest even
on the bits, and the products summed over three passes (hi*hi + hi*lo +
lo*hi), which is what XLA's ``high`` precision computes on a TPU.  The
split is written on integer bits so that no backend can drop it as a
round trip through bfloat16; each pass multiplies bfloat16 values at
full precision, so it is exact.  ``"high"`` asks XLA for its own
``high`` precision (three passes on a TPU, full float32 on a CPU), to
check the written split against it on the chip.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def layers(detector):
    """3x3 stride-1 convs from 3 channels through ``channels`` (the first
    in the entry kernel, the rest in the stack megakernel), then the 1x1
    head of ``num_anchors * 5`` outputs, whose rows the changed-only
    scatter writes into the head canvas."""
    chans = (3,) + tuple(detector["channels"])
    out, prev = [], "frame"
    for i, (ci, co) in enumerate(zip(chans[:-1], chans[1:])):
        out.append({"name": f"conv{i}", "op": "conv", "k": 3, "stride": 1,
                    "cin": ci, "cout": co, "stride_in": 1, "inputs": [prev],
                    "role": "roi_conv_entry" if i == 0 else "roi_conv_stack"})
        prev = f"conv{i}"
    out.append({"name": "head", "op": "head", "k": 1, "stride": 1,
                "cin": chans[-1], "cout": detector["num_anchors"] * 5,
                "stride_in": 1, "inputs": [prev],
                "role": "sbnet_scatter_changed"})
    return out


def init(key, detector):
    """Weights from ``key``: conv layer i from ``fold_in(key, i)``, the
    head from ``fold_in(key, 99)``, each normal / sqrt(fan-in)."""
    return _init(key, tuple(detector["channels"]), detector["num_anchors"])


@partial(jax.jit, static_argnums=(1, 2))
def _init(key, channels, num_anchors):
    chans = (3,) + channels
    ws = []
    for i, (ci, co) in enumerate(zip(chans[:-1], chans[1:])):
        w = jax.random.normal(jax.random.fold_in(key, i), (3, 3, ci, co),
                              jnp.float32)
        ws.append(w / np.sqrt(9 * ci))
    head = jax.random.normal(jax.random.fold_in(key, 99),
                             (chans[-1], num_anchors * 5), jnp.float32)
    return {"convs": ws, "head": head / np.sqrt(chans[-1])}


def _bf16(a):
    """``a`` rounded to the nearest bfloat16 (ties to even), as float32,
    computed on the bits."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _product(f, a, b, passes):
    if passes == "highest":
        return f(a, b, HI)
    if passes == "high":
        return f(a, b, jax.lax.Precision.HIGH)
    if passes != "bf16x3":
        raise ValueError(f"unknown precision passes {passes!r}")
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return f(ah, bh, HI) + f(ah, bl, HI) + f(al, bh, HI)


def _conv(x, w, precision):
    return jax.lax.conv_general_dilated(
        x[None], w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)[0]


def _dot(x, w, precision):
    return jnp.dot(x, w, precision=precision)


@partial(jax.jit, static_argnames=("passes",))
def forward(params, frame, mask, passes="highest"):
    """(H, W, 3) frame, (H, W, 1) bool pixel mask -> (H, W, A) heads."""
    x = frame
    for w in params["convs"]:
        x = jnp.where(mask, jax.nn.relu(_product(_conv, x, w, passes)), 0.0)
    return jnp.where(mask, _product(_dot, x, params["head"], passes), 0.0)


def pixel_mask(grid, tile, shape):
    """Detector-tile bool grid -> (H, W, 1) pixel mask of a frame."""
    px = np.kron(np.asarray(grid, bool), np.ones((tile, tile), bool))
    full = np.zeros(shape[:2], bool)
    h, w = min(px.shape[0], shape[0]), min(px.shape[1], shape[1])
    full[:h, :w] = px[:h, :w]
    return full[..., None]
