"""A strided residual detector's plain reference, for the test that adds
a configuration by files alone: a 3x3 stride-1 conv, a 3x3 stride-2
conv beside a 1x1 stride-2 projection, their sum, and a 3x3 head at
stride 2, in float32 ``jax.numpy`` with SAME padding.  Every layer's
output is zeroed outside the active tiles at its own stride."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def layers(detector):
    c1, c2 = detector["widths"]
    a = detector["num_anchors"] * 5
    return [
        {"name": "stem", "op": "conv", "k": 3, "stride": 1, "cin": 3,
         "cout": c1, "stride_in": 1, "inputs": ["frame"],
         "role": "roi_conv_entry"},
        {"name": "down", "op": "conv", "k": 3, "stride": 2, "cin": c1,
         "cout": c2, "stride_in": 1, "inputs": ["stem"],
         "role": "roi_conv_stack"},
        {"name": "proj", "op": "conv", "k": 1, "stride": 2, "cin": c1,
         "cout": c2, "stride_in": 1, "inputs": ["stem"],
         "role": "roi_conv_stack"},
        {"name": "sum", "op": "add", "k": 1, "stride": 1, "cin": c2,
         "cout": c2, "stride_in": 2, "inputs": ["down", "proj"],
         "role": "roi_conv_stack"},
        {"name": "head", "op": "head", "k": 3, "stride": 1, "cin": c2,
         "cout": a, "stride_in": 2, "inputs": ["sum"],
         "role": "sbnet_scatter_changed"},
    ]


def init(key, detector):
    shapes = tuple((layer["k"], layer["k"], layer["cin"], layer["cout"])
                   for layer in layers(detector) if layer["op"] != "add")
    return _init(key, shapes)


@partial(jax.jit, static_argnums=1)
def _init(key, shapes):
    return [jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32)
            / np.sqrt(s[0] * s[1] * s[2]) for i, s in enumerate(shapes)]


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x[None], w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)[0]


@partial(jax.jit, static_argnames=("passes",))
def forward(params, frame, mask, passes="highest"):
    if passes != "highest":
        raise ValueError(f"no precision passes {passes!r} here")
    stem, down, proj, head = params
    m2 = mask[::2, ::2]
    x = jnp.where(mask, jax.nn.relu(_conv(frame, stem, 1)), 0.0)
    y = jax.nn.relu(_conv(x, down, 2) + _conv(x, proj, 2))
    y = jnp.where(m2, y, 0.0)
    return jnp.where(m2, _conv(y, head, 1), 0.0)


def pixel_mask(grid, tile, shape):
    px = np.kron(np.asarray(grid, bool), np.ones((tile, tile), bool))
    full = np.zeros(shape[:2], bool)
    h, w = min(px.shape[0], shape[0]), min(px.shape[1], shape[1])
    full[:h, :w] = px[:h, :w]
    return full[..., None]
