"""Entry: every group on one device through ``fleet.runtime.fleet_reuse_step``
with one ``PackedActivationCache`` (the delta-gated super-launch).

A step puts the host frames on the device, calls the fleet step and
waits for every head map it returned.  The heads stay device arrays.
"""
import time

import jax

from repro.fleet.runtime import fleet_reuse_step
from repro.serving.detector import PackedActivationCache


class Entry:
    def __init__(self, det, grids, devices, threshold):
        self.det = det
        self.grids = grids
        self.device = devices[0]
        self.threshold = threshold
        self.cache = PackedActivationCache()

    def step(self, frames, span):
        """frames: {gid: [(H, W, 3) float32 numpy]} -> (heads {gid: [...]},
        the program's step stats, seconds the fleet-step call held the
        host)."""
        with span("upload"):
            dev = {g: jax.device_put(fs, self.device)
                   for g, fs in frames.items()}
        t0 = time.perf_counter()
        with span("fleet_step"):
            outs, _, stats = fleet_reuse_step(self.det, dev, self.grids,
                                              self.cache, self.threshold)
        host_s = time.perf_counter() - t0
        with span("wait"):
            jax.block_until_ready(outs)
        return outs, stats, host_s
