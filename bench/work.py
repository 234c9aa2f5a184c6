"""Logical work of a configuration, and the chip's peaks.

Each family (``bench/families/<family>.py``) lists its configuration's
work per kernel call as :class:`LayerWork` entries (``layers(cfg)``).
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
import pathlib
from typing import List

import readers

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class LayerWork:
    name: str
    kernel: str           # a key of the family's KERNELS
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
    """The work of ``cfg`` per kernel call, in the order the plan calls
    its kernels, as its family lists it."""
    return readers.family(cfg).layers(cfg)


def ops_per_image(cfg: dict, kernel: str = None) -> float:
    """Logical operations per image, over all layers or one kernel's."""
    return sum(2.0 * l.macs for l in layers(cfg)
               if kernel is None or l.kernel == kernel)


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; raises for a device the
    table does not hold."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}: {sorted(table)}")
    return table[device_kind]


def roofline_share(run, kernel: str):
    """Percent: the least time of the logical work of the layers that run
    on ``kernel`` in every server call in the trace
    (``run.trace["kernels"]``), over those kernels' device time.  Calls
    whose event count differs from the layer count are left out; None
    where no call is left, or the family has no such kernel."""
    tr = run.trace
    if not tr:
        return None
    lays = [l for l in layers(run.cfg) if l.kernel == kernel]
    least = device = 0.0
    for images, durs in tr["kernels"].get(kernel, []):
        if len(durs) != len(lays):
            continue
        per_chip = images / run.chips
        least += sum(l.least_time_s(per_chip, run.peak) for l in lays)
        device += sum(durs) / 1e9
    if device <= 0:
        return None
    return 100.0 * least / device
