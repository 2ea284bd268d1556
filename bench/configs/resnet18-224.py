"""Plain INT8 reference of ResNet-18, built from ``resnet18-224.json``.

He et al. 2016, Table 1 (torchvision ``resnet18``): a 7x7/2 stem
convolution with relu and a 3x3/2 max-pool, four stages of two basic
blocks (3x3 conv, relu, 3x3 conv, residual add, relu; a 1x1/2
projection where the shape changes), a global average pool and a
1000-way linear layer.  Batch norm is folded into the convolutions
(their weights stand for the folded ones), so a convolution has no
bias; the linear layer has one.

Each layer requantizes its accumulator with its own shift; the average
pool requantizes the channel sums with the shift of the convolution it
follows and the window size as divisor.  Tensors are named after the
layers that produce them: ``maxpool``, ``layer2.0.conv1`` (post relu),
``layer2.0.conv2`` and ``layer2.0.down`` (requantized, before the add),
``layer2.0`` (the block's output), ``avgpool`` and ``fc``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np

import refcnn

# the seeded draws: small weights and biases, so that a few fused layers
# keep an int8 signal after calibration
W_RANGE = (-6, 7)
BIAS_RANGE = (-40, 40)
IMAGE_RANGE = (-8, 8)
TARGET_STD = 32


def layers(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Every matmul layer in execution order, with its shapes and MACs
    for one image."""
    out: List[Dict[str, Any]] = []

    def conv(name: str, hw: int, cin: int, cout: int, k: int,
             stride: int, pad: int) -> int:
        ho = (hw + 2 * pad - k) // stride + 1
        out.append({"name": name, "kind": "conv", "k": k, "stride": stride,
                    "pad": pad, "cin": cin, "cout": cout, "hw": hw,
                    "out_hw": ho, "m": ho * ho, "kdim": k * k * cin,
                    "n": cout, "macs": ho * ho * k * k * cin * cout})
        return ho

    st = cfg["stem"]
    hw = conv("conv1", cfg["res"], cfg["in_channels"], st["cout"], st["k"],
              st["stride"], st["pad"])
    hw = (hw + 2 * st["pool_pad"] - st["pool_k"]) // st["pool_stride"] + 1
    cin = st["cout"]
    for si, (cout, stride) in enumerate(cfg["stages"], start=1):
        for b in range(cfg["blocks_per_stage"]):
            s = stride if b == 0 else 1
            name = f"layer{si}.{b}"
            ho = conv(f"{name}.conv1", hw, cin, cout, 3, s, 1)
            conv(f"{name}.conv2", ho, cout, cout, 3, 1, 1)
            if s != 1 or cin != cout:
                conv(f"{name}.down", hw, cin, cout, 1, s, 0)
            hw, cin = ho, cout
    out.append({"name": "fc", "kind": "linear", "cin": cin,
                "cout": cfg["n_classes"], "m": 1, "kdim": cin,
                "n": cfg["n_classes"], "macs": cin * cfg["n_classes"]})
    return out


def make_params(cfg: Dict[str, Any], rng: np.random.Generator
                ) -> Dict[str, np.ndarray]:
    """Seeded int8 kernels ``(k, k, cin, cout)``, the linear layer's
    ``(cin, cout)`` matrix and its int32 bias (``fc.bias``)."""
    p: Dict[str, np.ndarray] = {}
    for ly in layers(cfg):
        if ly["kind"] == "conv":
            shape = (ly["k"], ly["k"], ly["cin"], ly["cout"])
        else:
            shape = (ly["cin"], ly["cout"])
            p["fc.bias"] = rng.integers(*BIAS_RANGE, ly["cout"]
                                        ).astype(np.int32)
        p[ly["name"]] = rng.integers(*W_RANGE, shape, dtype=np.int8)
    return p


def make_image(cfg: Dict[str, Any], rng: np.random.Generator) -> np.ndarray:
    return rng.integers(*IMAGE_RANGE, (cfg["res"], cfg["res"],
                                       cfg["in_channels"])).astype(np.int8)


def _shift_for(acc: np.ndarray) -> int:
    std = max(1.0, float(np.std(acc)))
    return min(30, max(0, round(math.log2(std / TARGET_STD))))


def _pool(x: np.ndarray, shift: int) -> np.ndarray:
    """The global average pool fused into the last block: its channel
    sums requantized with the shift of the block's last convolution and
    the window size as divisor."""
    return refcnn.requant(refcnn.gap_sum(x), shift,
                          div=x.shape[0] * x.shape[1])


def _pool_shift(acc: np.ndarray, skip: np.ndarray) -> int:
    """The last convolution's shift, sized on what its group emits.

    The pool fused into its group applies the shift a second time, so a
    shift sized on the accumulator leaves the pooled vector, and with it
    the classifier's input, all but zero.  The group's output is the
    pooled vector, so the shift is the one whose pooled vector has the
    standard deviation nearest ``TARGET_STD`` (the smallest such)."""
    def spread(s: int) -> float:
        y = refcnn.relu(refcnn.sat_add(refcnn.requant(acc, s), skip))
        std = float(np.std(_pool(y, s)))
        return abs(math.log2(max(std, 1e-3) / TARGET_STD))
    return min(range(31), key=spread)


def forward(cfg: Dict[str, Any], p: Dict[str, np.ndarray],
            shifts: Dict[str, int], x: np.ndarray,
            operand_bits: Optional[int] = None,
            calibrating: bool = False) -> Dict[str, np.ndarray]:
    """One image through the network; every named tensor comes back.

    With ``calibrating``, each layer's shift is chosen from its own
    accumulator (before the bias) as the pass reaches it, the last
    convolution's from the pooled vector (:func:`_pool_shift`), and
    written into ``shifts``."""
    t: Dict[str, np.ndarray] = {}

    def shift(name: str, acc: np.ndarray) -> int:
        if calibrating:
            shifts[name] = _shift_for(acc)
        return shifts[name]

    def conv(name: str, x: np.ndarray, stride: int, pad: int) -> np.ndarray:
        acc = refcnn.conv_acc(x, p[name], stride, pad, operand_bits)
        return refcnn.requant(acc, shift(name, acc))

    st = cfg["stem"]
    y = refcnn.relu(conv("conv1", x, st["stride"], st["pad"]))
    t["conv1"] = y
    x = t["maxpool"] = refcnn.maxpool(y, st["pool_k"], st["pool_stride"],
                                      st["pool_pad"])
    cin = st["cout"]
    last = None
    for si, (cout, stride) in enumerate(cfg["stages"], start=1):
        for b in range(cfg["blocks_per_stage"]):
            s = stride if b == 0 else 1
            name = f"layer{si}.{b}"
            h = t[f"{name}.conv1"] = refcnn.relu(
                conv(f"{name}.conv1", x, s, 1))
            if s != 1 or cin != cout:
                skip = t[f"{name}.down"] = conv(f"{name}.down", x, s, 0)
            else:
                skip = x
            last = f"{name}.conv2"
            final = (si, b) == (len(cfg["stages"]),
                                cfg["blocks_per_stage"] - 1)
            acc = refcnn.conv_acc(h, p[last], 1, 1, operand_bits)
            if calibrating and final:
                shifts[last] = _pool_shift(acc, skip)
            y = t[last] = refcnn.requant(
                acc, shifts[last] if final else shift(last, acc))
            x = t[name] = refcnn.relu(refcnn.sat_add(y, skip))
            cin = cout
    t["avgpool"] = _pool(x, shifts[last])
    acc = refcnn.linear_acc(t["avgpool"], p["fc"], np.zeros(
        p["fc"].shape[1], np.int64), operand_bits)
    t["fc"] = refcnn.requant(acc + p["fc.bias"], shift("fc", acc))
    return t


def calibrate(cfg: Dict[str, Any], p: Dict[str, np.ndarray],
              x: np.ndarray) -> Dict[str, int]:
    """Per-layer shifts that bring each accumulator's standard deviation
    on the calibration image near ``TARGET_STD`` after requantization,
    so that the layers keep an int8 signal (a rare value saturates).
    One pass, layer by layer, since a layer's range depends on the
    shifts before it."""
    shifts: Dict[str, int] = {}
    forward(cfg, p, shifts, x, calibrating=True)
    return shifts
