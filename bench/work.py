"""Logical work of a configuration, and the chip's peaks.

Work counts what the network needs, whatever implements it, so that no
change to padding, tiling or dataflow alone can raise a roofline share:

* operations: one int8 multiply-accumulate (2 operations) per weight and
  output position, from the unpadded layer shapes; each layer counted
  once, whatever number of plane passes its dataflow makes;
* bytes: the unpadded uint8 input levels, the weights as stored (int8),
  and the output (uint8 levels, or int32 accumulators for the logits
  layer), each read or written once.

Peaks come from ``peaks.json``, keyed by ``device_kind``; a device that is
not in the table is an error.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from typing import List

import netgen

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class LayerWork:
    name: str
    kind: str             # "conv" or "linear"
    macs: int             # per image
    in_bytes: int         # per image
    out_bytes: int        # per image
    weight_bytes: int     # per call

    def ops(self, images: int) -> float:
        return 2.0 * self.macs * images

    def bytes(self, images: int) -> float:
        return (self.in_bytes + self.out_bytes) * images + self.weight_bytes

    def least_time_s(self, images: int, peak: dict) -> float:
        """The least time the chip could take for one call over
        ``images`` images: compute-bound or memory-bound, the larger."""
        return max(self.ops(images) / peak["int8_ops_per_s"],
                   self.bytes(images) / peak["hbm_bytes_per_s"])


def layers(cfg: dict) -> List[LayerWork]:
    """Per conv / linear layer of ``cfg``, in order."""
    wshapes, outs = netgen.layer_shapes(cfg)
    shape = tuple(cfg["input_hw"])
    n_affine = sum(s is not None for s in wshapes)
    out, seen = [], 0
    for layer, wshape, oshape in zip(cfg["layers"], wshapes, outs):
        if wshape is not None:
            seen += 1
            positions = math.prod(oshape[:-1])
            fan_in = math.prod(wshape[:-1])
            out_elem = 4 if seen == n_affine else 1
            out.append(LayerWork(
                name=f"{layer['kind']}{seen}",
                kind=layer["kind"],
                macs=positions * fan_in * wshape[-1],
                in_bytes=math.prod(shape),
                out_bytes=math.prod(oshape) * out_elem,
                weight_bytes=math.prod(wshape)))
        shape = oshape
    return out


def ops_per_image(cfg: dict, kind: str = None) -> float:
    """Logical operations per image, over all layers or one kind."""
    return sum(2.0 * l.macs for l in layers(cfg)
               if kind is None or l.kind == kind)


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; raises for a device the
    table does not hold."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}: {sorted(table)}")
    return table[device_kind]


KERNEL_LAYERS = {"conv": "conv", "matmul": "linear"}


def roofline_share(run, kernel: str):
    """Percent: the least time of the logical work of the ``kernel``
    layers of every server call in the trace (``run.trace["kernels"]``),
    over those kernels' device time.  Calls whose event count differs from
    the layer count are left out; None where no call is left."""
    tr = run.trace
    if not tr:
        return None
    lays = [l for l in layers(run.cfg) if l.kind == KERNEL_LAYERS[kernel]]
    least = device = 0.0
    for images, durs in tr["kernels"][kernel]:
        if len(durs) != len(lays):
            continue
        per_chip = images / run.chips
        least += sum(l.least_time_s(per_chip, run.peak) for l in lays)
        device += sum(durs) / 1e9
    if device <= 0:
        return None
    return 100.0 * least / device
