"""Entry: groups sharded over a 1-D fleet mesh of every device the cell
holds, through ``fleet.runtime.sharded_fleet_step`` over a
``fleet.sharded.ShardedSuperlaunch``.

The program stacks the host frames onto its per-shard canvas, runs one
SPMD program per kernel and returns the heads as host arrays, so the
fleet-step call holds the host until the device is done: the step has
no upload or wait phase of its own.
"""
import time

from entries.fleet_reuse import roi_detector
from repro.fleet.runtime import sharded_fleet_step
from repro.fleet.sharded import ShardedSuperlaunch
from repro.launch.mesh import make_fleet_mesh


class Entry:
    def __init__(self, detector, params, grids, devices, threshold):
        self.rt = ShardedSuperlaunch(roi_detector(detector, params), grids,
                                     make_fleet_mesh(len(devices)))
        self.cache = self.rt.make_cache()
        self.threshold = threshold

    def step(self, frames, span):
        t0 = time.perf_counter()
        with span("fleet_step"):
            outs, _, stats = sharded_fleet_step(self.rt, frames, self.cache,
                                                self.threshold)
        return outs, stats, time.perf_counter() - t0
