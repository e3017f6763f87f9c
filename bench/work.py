"""Work a configuration asks of the chip, counted from its layer shapes.

The counts are of the network's layers, not of what the program launches:
padded lanes, the block-diagonal form of a depthwise weight and the im2col
copies are the program's choice and count for nothing here, so a share
computed from these numbers cannot pass 100% whatever the implementation
does.

* FLOPs are 2 x multiply-accumulates.
* Bytes are the layer's input (the map it reads before SAME padding,
  ``P*stride x Q*stride x C_in``), its weights and its output, each once,
  at float32; weights are read once per batch, activations once per
  sample.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from typing import Dict, Sequence

F32 = 4


class SetupError(Exception):
    """The run cannot be made here; no result is printed.  Raised by the
    harness and by a system module alike."""


def in_channels(layer: Dict) -> int:
    """Channels a layer reads: its own channels when depthwise."""
    return layer["M"] if layer.get("depthwise") else layer["C"]


def in_hw(layer: Dict):
    """The input canvas a valid convolution of this layer reads."""
    s = layer["stride"]
    return (layer["P"] - 1) * s + layer["R"], (layer["Q"] - 1) * s + layer["S"]


def macs(layer: Dict) -> int:
    """Multiply-accumulates of one sample through one layer."""
    reduce = 1 if layer.get("depthwise") else layer["C"]
    return (layer["M"] * reduce * layer["P"] * layer["Q"]
            * layer["R"] * layer["S"])


def weight_params(layer: Dict) -> int:
    reduce = 1 if layer.get("depthwise") else layer["C"]
    return layer["R"] * layer["S"] * reduce * layer["M"]


@dataclasses.dataclass(frozen=True)
class Work:
    """Per-sample FLOPs and activation bytes, and per-batch weight bytes."""

    flops: float
    act_bytes: float
    weight_bytes: float

    def least_seconds(self, samples: int, batches: int, peak: Dict) -> float:
        """The least time the chip could take for ``samples`` served in
        ``batches`` batches: the larger of FLOPs over peak FLOP/s and bytes
        over peak HBM bandwidth."""
        flops = samples * self.flops
        moved = samples * self.act_bytes + batches * self.weight_bytes
        return max(flops / peak["flops_per_s"], moved / peak["hbm_bytes_per_s"])


def layer_work(layer: Dict) -> Work:
    s = layer["stride"]
    act = (layer["P"] * s * layer["Q"] * s * in_channels(layer)
           + layer["P"] * layer["Q"] * layer["M"])
    return Work(flops=2.0 * macs(layer), act_bytes=float(F32 * act),
                weight_bytes=float(F32 * weight_params(layer)))


def network_work(layers: Sequence[Dict]) -> Work:
    per = [layer_work(layer) for layer in layers]
    return Work(flops=sum(w.flops for w in per),
                act_bytes=sum(w.act_bytes for w in per),
                weight_bytes=sum(w.weight_bytes for w in per))


def batches_for(samples: int, max_batch: int) -> int:
    """Batches that serve ``samples`` when every batch is full."""
    return math.ceil(samples / max_batch)


_PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peak_for(device_kind: str, path: pathlib.Path = _PEAKS) -> Dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    table = json.loads(path.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(k for k in table if k != 'source')}")
    return table[device_kind]
