"""Compile rehearsal: every served kernel through Mosaic for a TPU v5e.

The Pallas interpreter the rest of the suite runs accepts what Mosaic
refuses (unaligned blocks, lane-sliced DMAs, oversized VMEM).  These
tests compile each kernel of the fleet step with ``interpret=False`` for
one chip of a described (not attached) v5e at the intersection shapes
``chip_smoke.py`` serves: five cameras stacked on a 544x960 canvas of
16-px tiles, the default ``DetectorConfig`` widths, 4096 active tiles.
Each compiled module must hold the Mosaic kernel (``tpu_custom_call``).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.blocking import pad_frames, window_width
from repro.kernels.roi_attention import roi_attention
from repro.kernels.roi_conv import roi_conv_entry, roi_conv_stack
from repro.kernels.sbnet import sbnet_scatter_changed, sbnet_scatter_fleet
from repro.kernels.tile_delta import tile_delta_gate_canvas
from repro.serving.detector import DetectorConfig

CAMS, H, W, T = 5, 544, 960, 16
N_TILES = 4096
CFG = DetectorConfig()
CHANS = (3,) + CFG.channels
HEAD = CFG.num_anchors * 5
BLOCK = ops.choose_block(T, T, max(CHANS), len(CFG.channels),
                         CFG.vmem_budget_bytes)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32, I32 = jnp.float32, jnp.int32


def test_gate_canvas_compiles(one_chip):
    padded = (CAMS, H + 2, W + 2 + window_width(T) - T - 2, 3)
    _compile(lambda xp, ref, idx: tile_delta_gate_canvas(
        xp, ref, idx, T, T, block=BLOCK, interpret=False), one_chip,
        (padded, F32), (padded, F32), ((N_TILES, 3), I32))


@pytest.mark.parametrize("block", [1, BLOCK])
def test_entry_compiles(one_chip, block):
    _compile(lambda x, w, idx: roi_conv_entry(
        x, w, idx, T, T, block=block, interpret=False), one_chip,
        ((CAMS, H, W, 3), F32), ((3, 3, 3, CHANS[1]), F32),
        ((N_TILES, 3), I32))


def test_stack_compiles(one_chip):
    ws = [((3, 3, ci, co), F32) for ci, co in zip(CHANS[1:-1], CHANS[2:])]
    _compile(lambda p, nbr, *w: roi_conv_stack(
        p, list(w), nbr, block=BLOCK, interpret=False), one_chip,
        ((N_TILES, T, T, CHANS[1]), F32), ((N_TILES, 8), I32), *ws)


def test_scatter_fleet_compiles(one_chip):
    _compile(lambda p, idx, base: sbnet_scatter_fleet(
        p, idx, base, interpret=False), one_chip,
        ((N_TILES, T, T, HEAD), F32), ((N_TILES, 3), I32),
        ((CAMS, H, W, HEAD), F32))


def test_scatter_changed_compiles(one_chip):
    _compile(lambda p, idx, base: sbnet_scatter_changed(
        p, idx, base, interpret=False), one_chip,
        ((64, T, T, HEAD), F32), ((64, 3), I32), ((CAMS, H, W, HEAD), F32))


def test_roi_attention_compiles(one_chip):
    s, h, d = 256, 2, 128
    _compile(lambda q, k, v, pos: roi_attention(
        q, k, v, pos, interpret=False), one_chip,
        ((s, h, d), F32), ((s, h, d), F32), ((s, h, d), F32), ((s,), I32))


def test_pad_frames_matches_gate_shape():
    """The canvas the detector pads is the shape the gate was compiled
    for above (no widening copy on the served path)."""
    x = jax.ShapeDtypeStruct((CAMS, H, W, 3), F32)
    assert jax.eval_shape(lambda a: pad_frames(a, T), x).shape == \
        (CAMS, H + 2, W + 2 + window_width(T) - T - 2, 3)
