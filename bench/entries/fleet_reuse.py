"""Entry: every group on one device through ``fleet.runtime.fleet_reuse_step``
with one ``PackedActivationCache`` (the delta-gated super-launch).

An entry builds the program's detector from the configuration's
``detector`` dict and the weights the reference drew
(``Entry(detector, params, grids, devices, threshold)``).  A step puts
the host frames on the device, calls the fleet step and waits for every
head map it returned.  The heads stay device arrays.
"""
import time

import jax

from repro.fleet.runtime import fleet_reuse_step
from repro.serving.detector import (DetectorConfig, PackedActivationCache,
                                    RoIDetector)


def roi_detector(detector, params):
    """``RoIDetector`` of the ``detector`` dict, holding ``params`` (the
    conv stack and head of ``references/roi_detector.py``)."""
    cfg = DetectorConfig(**dict(detector,
                                channels=tuple(detector["channels"])))
    det = RoIDetector(cfg, jax.random.PRNGKey(0))
    det.weights = list(params["convs"])
    det.head = params["head"]
    return det


class Entry:
    def __init__(self, detector, params, grids, devices, threshold):
        self.det = roi_detector(detector, params)
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
