"""An entry in plain ``jax.numpy`` for ``strided_res``'s detector, for
the test that adds a configuration by files alone: every active tile
recomputed each step, each SAME-padded conv written as a sum over its
kernel's taps of strided slices of the padded input."""
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np


def _conv(x, w, stride):
    k = w.shape[0]
    lo = max(k - stride, 0) // 2
    hi = max(k - stride, 0) - lo
    h, wd = x.shape[0] // stride, x.shape[1] // stride
    xp = jnp.pad(x, ((lo, hi), (lo, hi), (0, 0)))
    out = 0.0
    for dy in range(k):
        for dx in range(k):
            tap = xp[dy:dy + stride * h:stride, dx:dx + stride * wd:stride]
            out = out + jnp.einsum("hwc,cd->hwd", tap, w[dy, dx],
                                   precision=jax.lax.Precision.HIGHEST)
    return out


@jax.jit
def _forward(params, frame, mask):
    stem, down, proj, head = params
    m2 = mask[::2, ::2]
    x = jnp.where(mask, jax.nn.relu(_conv(frame, stem, 1)), 0.0)
    y = jnp.where(m2, jax.nn.relu(_conv(x, down, 2) + _conv(x, proj, 2)),
                  0.0)
    return jnp.where(m2, _conv(y, head, 1), 0.0)


class Entry:
    def __init__(self, detector, params, grids, devices, threshold):
        px = np.ones((detector["tile"],) * 2, bool)
        self.params = params
        self.masks = {g: [jnp.asarray(np.kron(a, px)[..., None]) for a in gs]
                      for g, gs in grids.items()}
        n = sum(int(np.sum(a)) for gs in grids.values() for a in gs)
        self.stats = SimpleNamespace(launched=n, computed=n)

    def step(self, frames, span):
        t0 = time.perf_counter()
        outs = {g: [_forward(self.params, jnp.asarray(f), m)
                    for f, m in zip(fs, self.masks[g])]
                for g, fs in frames.items()}
        host_s = time.perf_counter() - t0
        jax.block_until_ready(outs)
        return outs, self.stats, host_s
