"""Generator ``scene_motion``: recorded scene motion replayed as frames.

A traffic mix (``bench/traffic/<mix>.json``) names its generator, a
module ``bench/generators/<generator>.py`` whose ``Generator`` the
harness builds as ``Generator(scenes, params, scale, tile, seed, rings)``,
where ``rings`` is how many tile rings the detector's receptive field
crosses (``harness.layers.rings`` of the reference's layer list).  This
one reads these parameters:

* ``span``: [first frame, frame count] of the recorded online phase to
  replay.  The walk goes forward through the span and back (ping-pong,
  period 2 * count - 2 steps), so a run never runs out of frames and no
  wrap-around jump appears.  One step advances the scene by one frame
  (0.1 s at the recorded 10 fps).  Each step gives fresh pixels to every
  detector tile (active or not) that intersects a vehicle box of that
  camera at the new frame or the previous one; every other pixel stays
  bit-identical to the previous frame, as the skipped macroblocks of a
  decoded H.264/H.265 stream do.
* ``patches``: size of the bank of fresh 16x16 patches, drawn once from
  the seed; the patch a tile gets is a function of the step, camera and
  tile, so a tile redrawn twice within ``patches`` steps always changes.
  Pixels, background and bank alike, are standard-normal float32.

The seed sets the pixels only: every run walks the same transitions in
the same order from the start of the span, so the sizes the program sees
are the same for every seed.  The generator also reports, per step, the
tiles whose input changed and the useful tiles: active tiles whose head
output depends on a changed pixel.  A head pixel reads at most
``rf_px`` frame pixels beyond its own (``harness.layers.rf_px``), so
those are the changed tiles dilated by ``rings`` = ceil(rf_px / tile)
rings, within the active set: one ring for three 3x3 stride-1 layers
(3 px) at 16-px tiles, several for a deep strided backbone.

What the harness reads of a generator: ``frames`` ({group: [(H, W, 3)
float32]}, edited in place), ``grids`` ({group: [tile bool grid]}),
``cameras``, ``n_active``, ``period`` (steps after which the walk
repeats), ``step``, ``advance()`` -> (changed, useful), ``transition(i)``
-> (tiles, changed, useful) of walk step ``i``, and ``snapshot()``.
"""
import numpy as np


def detector_grid(grid, cam_size, offline_tile, scale, tile):
    """One camera's offline mask -> detector-tile grid at ``scale``."""
    w, h = (int(v) for v in cam_size)
    ty, tx = -(-h // offline_tile), -(-w // offline_tile)
    k = offline_tile * scale / tile
    if k != int(k):
        raise ValueError(f"offline tile {offline_tile} px at scale {scale} "
                         f"is not a whole number of {tile}-px tiles")
    k = int(k)
    return np.kron(np.asarray(grid, bool)[:ty, :tx], np.ones((k, k), bool))


def box_tiles(boxes, frames, n_frames, shape, px_per_tile):
    """(n, 6) boxes of one camera -> (n_frames, TY, TX) bool: the tiles
    each frame's boxes intersect (box coordinates in full-res px)."""
    out = np.zeros((n_frames,) + tuple(shape), bool)
    ty_max, tx_max = shape
    for f, x0, y0, x1, y1 in zip(frames, boxes[:, 0], boxes[:, 1],
                                 boxes[:, 2], boxes[:, 3]):
        a, b = y0 // px_per_tile, min((y1 - 1) // px_per_tile, ty_max - 1)
        c, d = x0 // px_per_tile, min((x1 - 1) // px_per_tile, tx_max - 1)
        out[f, max(a, 0):b + 1, max(c, 0):d + 1] = True
    return out


def dilate(m, rings):
    """``rings`` 8-neighbour rings of dilation of a 2-D bool array."""
    p = np.pad(m, rings)
    out = np.zeros_like(m)
    h, w = m.shape
    for dy in range(2 * rings + 1):
        for dx in range(2 * rings + 1):
            out |= p[dy:dy + h, dx:dx + w]
    return out


def pingpong(i, n):
    """Position of walk step ``i`` on a span of ``n`` frames."""
    period = max(2 * n - 2, 1)
    i %= period
    return i if i < n else period - i


class Generator:
    """Frames for every camera of every group, advanced one scene frame
    per ``advance()``.  ``frames`` holds the current host arrays, which
    ``advance`` edits in place."""

    def __init__(self, scenes, params, scale, tile, seed, rings):
        rng = np.random.default_rng(seed)
        self.tile = tile
        self.rings = rings
        f0, n = (int(v) for v in params["span"])
        self.span_len = n
        self.period = max(2 * n - 2, 1)
        bank = int(params["patches"])
        self.bank = rng.standard_normal((bank, tile, tile, 3), np.float32)
        self.grids = {}        # gid -> [detector-tile bool grid per camera]
        self.frames = {}       # gid -> [(H, W, 3) float32]
        self._boxes = []       # flat camera -> (n, TY, TX) box tiles
        self._views = []       # flat camera -> (TY, TX, t, t, 3) view
        px = int(round(tile / scale))
        for gid, sc in enumerate(scenes):
            span = sc["span"]
            if f0 < span[0] or f0 + n > span[1]:
                raise ValueError(f"span {params['span']} outside the "
                                 f"recorded frames {span.tolist()}")
            gs, fs = [], []
            for c, size in enumerate(sc["cam_size"]):
                g = detector_grid(sc["grids"][c], size,
                                  int(sc["offline_tile"]), scale, tile)
                b = sc["boxes"]
                sel = (b[:, 1] == c) & (b[:, 0] >= f0) & (b[:, 0] < f0 + n)
                self._boxes.append(box_tiles(b[sel, 2:], b[sel, 0] - f0, n,
                                             g.shape, px))
                f = rng.standard_normal((g.shape[0] * tile,
                                         g.shape[1] * tile, 3), np.float32)
                self._views.append(f.reshape(g.shape[0], tile, g.shape[1],
                                             tile, 3).transpose(0, 2, 1, 3, 4))
                gs.append(g)
                fs.append(f)
            self.grids[gid] = gs
            self.frames[gid] = fs
        self._active = [g for gs in self.grids.values() for g in gs]
        self.n_active = int(sum(g.sum() for g in self._active))
        self.cameras = len(self._active)
        self._salt = int(rng.integers(bank))
        self.step = 0
        self._transitions = [self._transition(j) for j in range(self.period)]
        # the first frame: background with the vehicles of the span's start
        for cam, view in enumerate(self._views):
            ys, xs = np.nonzero(self._boxes[cam][0])
            view[ys, xs] = self._patches(-1, cam, ys, xs)

    def _transition(self, j):
        """Walk step j: per camera the redrawn tiles, and the numbers of
        changed and useful tiles."""
        a = pingpong(j - 1, self.span_len)
        b = pingpong(j, self.span_len)
        tiles, changed, useful = [], 0, 0
        for cam, act in enumerate(self._active):
            ch = self._boxes[cam][a] | self._boxes[cam][b]
            tiles.append(np.nonzero(ch))
            changed += int((ch & act).sum())
            useful += int((dilate(ch, self.rings) & act).sum())
        return tiles, changed, useful

    def _patches(self, step, cam, ys, xs):
        idx = (step * 7919 + cam * 104729 + ys * 613 + xs * 31
               + self._salt) % self.bank.shape[0]
        return self.bank[idx]

    def transition(self, step):
        """(tiles, changed, useful) of walk step ``step`` of this run."""
        return self._transitions[step % self.period]

    def advance(self):
        """Move to the next scene frame; returns (changed, useful)."""
        self.step += 1
        tiles, changed, useful = self.transition(self.step)
        for cam, (ys, xs) in enumerate(tiles):
            if ys.size:
                self._views[cam][ys, xs] = self._patches(self.step, cam,
                                                         ys, xs)
        return changed, useful

    def snapshot(self):
        return {g: [f.copy() for f in fs] for g, fs in self.frames.items()}
