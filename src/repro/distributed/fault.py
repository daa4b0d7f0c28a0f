"""Fault tolerance: elastic re-meshing, straggler detection, fault injection.

Failure model at 1000+ nodes: a host dies or slows mid-run.  The recovery
path is launcher-level (the JAX SPMD program itself cannot drop a
participant mid-step): detect -> restore the latest checkpoint onto the
surviving device set (ElasticMesh picks the new shape) -> replay the data
stream deterministically from the restored step counter.  The train loop
wires these pieces together; tests/test_train_fault.py kills a run mid-step
with FaultInjector and asserts bit-exact continuation.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import numpy as np

from repro.launch.mesh import make_mesh


@dataclass
class ElasticMesh:
    """Builds the largest usable mesh from an available device count.

    Keeps the model axis fixed (TP degree is a property of the model fit)
    and shrinks/grows the data axis; at multi-pod scale the pod axis drops
    to 1 before the data axis shrinks (pod loss degrades gracefully to
    single-pod).
    """
    model_parallel: int
    prefer_pods: int = 1

    def shape_for(self, n_devices: int) -> Tuple[Tuple[int, ...],
                                                 Tuple[str, ...]]:
        tp = self.model_parallel
        if n_devices < tp:
            raise RuntimeError(
                f"{n_devices} devices cannot fit model axis {tp}")
        rest = n_devices // tp
        if self.prefer_pods > 1 and rest % self.prefer_pods == 0 \
                and rest >= 2 * self.prefer_pods:
            return ((self.prefer_pods, rest // self.prefer_pods, tp),
                    ("pod", "data", "model"))
        return ((rest, tp), ("data", "model"))

    def build(self, devices: Optional[list] = None):
        devices = devices if devices is not None else jax.devices()
        shape, axes = self.shape_for(len(devices))
        n = int(np.prod(shape))
        return make_mesh(shape, axes, devices=devices[:n])


@dataclass
class StragglerMonitor:
    """Per-step wall-time tracker with a robust deadline.

    deadline = median * tolerance over a sliding window; a step exceeding
    it is a straggler event.  At launcher level, persistent stragglers
    trigger the same restore-and-remesh path as failures (the slow host is
    excluded); in-process we record and surface them.
    """
    window: int = 50
    tolerance: float = 3.0
    min_samples: int = 5
    times: List[float] = field(default_factory=list)
    events: List[Tuple[int, float, float]] = field(default_factory=list)
    _t0: float = 0.0

    def start(self):
        self._t0 = time.monotonic()

    def stop(self, step: int) -> bool:
        dt = time.monotonic() - self._t0
        is_straggler = False
        if len(self.times) >= self.min_samples:
            deadline = float(np.median(self.times[-self.window:])) \
                * self.tolerance
            if dt > deadline:
                is_straggler = True
                self.events.append((step, dt, deadline))
        self.times.append(dt)
        return is_straggler

    @property
    def median_step_s(self) -> float:
        return float(np.median(self.times)) if self.times else 0.0


class InjectedFault(RuntimeError):
    pass


@dataclass
class FaultInjector:
    """Deterministically raise at configured steps (tests/chaos drills)."""
    fail_at_steps: Tuple[int, ...] = ()
    fired: set = field(default_factory=set)

    def check(self, step: int):
        if step in self.fail_at_steps and step not in self.fired:
            self.fired.add(step)
            raise InjectedFault(f"injected fault at step {step}")
