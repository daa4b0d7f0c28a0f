"""Arithmetic on a detector's layer list, as a configuration's reference
declares it (``layers(detector)`` of ``bench/references/<reference>.py``).

A layer is a dict:

* ``name``: unique within the list; ``inputs`` names earlier layers, or
  ``"frame"`` for the input frame;
* ``op``: ``conv`` (a k x k convolution, SAME padding), ``add`` (the sum
  of its inputs, which share one stride) or ``head`` (the output map, a
  k x k convolution like ``conv``);
* ``k``, ``stride``, ``cin``, ``cout``: kernel size, stride, input and
  output channels;
* ``stride_in``: the cumulative stride at its input, in frame pixels;
* ``role``: the kernel role (``bench/work/<role>.py``) whose work counts
  the layer, or None where no kernel's roofline reads it.

Counts are per detector tile of ``tile`` x ``tile`` frame pixels: a
layer whose output stride is ``s`` has ``(tile / s)**2`` output pixels
in a tile.  FLOPs are matmul FLOPs only (2 per multiply-add; an ``add``
counts none).
"""
import math

FRAME = "frame"


def stride_out(layer):
    return layer["stride_in"] * layer["stride"]


def px_per_tile(tile, stride):
    """Pixels of a map at ``stride`` that lie in one detector tile."""
    return (tile / stride) ** 2


def weights(layer):
    """Weights of a ``conv`` or ``head`` layer (0 for an ``add``)."""
    if layer["op"] == "add":
        return 0
    return layer["k"] ** 2 * layer["cin"] * layer["cout"]


def flops_per_tile(layer, tile):
    return 2 * weights(layer) * px_per_tile(tile, stride_out(layer))


def of_role(layers, role):
    return [layer for layer in layers if layer["role"] == role]


def heads(layers):
    return [layer for layer in layers if layer["op"] == "head"]


def rf_px(layers):
    """Receptive-field radius in frame pixels: how far beyond its own
    pixels (a block of ``stride_out`` frame pixels) a head pixel's input
    reaches, the larger of the two sides, over every path of the graph.

    Under SAME padding a k x k layer of stride s pads ``(k - s) // 2``
    before and ``k - s - (k - s) // 2`` after, so output pixel j reads
    input pixels ``j*s - lo`` to ``j*s - lo + k - 1``: it reaches ``lo``
    input pixels before its own and ``k - s - lo`` after, each
    ``stride_in`` frame pixels wide."""
    reach = {FRAME: (0, 0)}
    for layer in layers:
        before = max(reach[i][0] for i in layer["inputs"])
        after = max(reach[i][1] for i in layer["inputs"])
        if layer["op"] != "add":
            k, s, s_in = layer["k"], layer["stride"], layer["stride_in"]
            lo = max(k - s, 0) // 2
            before += s_in * lo
            after += s_in * (k - s - lo)
        reach[layer["name"]] = (before, after)
    return max([0] + [max(reach[h["name"]]) for h in heads(layers)])


def rings(layers, tile):
    """Tile rings by which a changed tile reaches the heads."""
    return math.ceil(rf_px(layers) / tile)


def check(layers):
    """Raise ``ValueError`` where the list is not a graph of layers in
    order whose ``stride_in`` follows from its inputs."""
    stride = {FRAME: 1}
    for layer in layers:
        if layer["name"] in stride:
            raise ValueError(f"layer name {layer['name']!r} used twice")
        ins = {stride.get(i) for i in layer["inputs"]}
        if None in ins or len(ins) != 1:
            raise ValueError(f"layer {layer['name']!r}: inputs "
                             f"{layer['inputs']} are not earlier layers "
                             f"of one stride")
        if ins != {layer["stride_in"]}:
            raise ValueError(f"layer {layer['name']!r}: stride_in "
                             f"{layer['stride_in']}, its inputs' {ins}")
        stride[layer["name"]] = stride_out(layer)
    if not heads(layers):
        raise ValueError("no head layer")
