"""Plain INT8 reference of the repository's ``tiny_cnn`` test graph
(3x3 conv + relu + 2x2 max-pool, 3x3 conv + relu + global average pool,
a linear layer with bias), for the CPU tests of the harness."""

import math

import numpy as np

import refcnn

TARGET_STD = 32


def layers(cfg):
    r, c, cin = cfg["res"], cfg["width"], cfg["in_channels"]
    h = r // 2
    return [
        {"name": "conv1", "kind": "conv", "k": 3, "cin": cin, "cout": c,
         "m": r * r, "kdim": 9 * cin, "n": c, "macs": r * r * 9 * cin * c},
        {"name": "conv2", "kind": "conv", "k": 3, "cin": c, "cout": 2 * c,
         "m": h * h, "kdim": 9 * c, "n": 2 * c,
         "macs": h * h * 9 * c * 2 * c},
        {"name": "fc", "kind": "linear", "cin": 2 * c,
         "cout": cfg["n_classes"], "m": 1, "kdim": 2 * c,
         "n": cfg["n_classes"], "macs": 2 * c * cfg["n_classes"]},
    ]


def make_params(cfg, rng):
    p = {}
    for ly in layers(cfg):
        shape = ((3, 3, ly["cin"], ly["cout"]) if ly["kind"] == "conv"
                 else (ly["cin"], ly["cout"]))
        p[ly["name"]] = rng.integers(-6, 7, shape, dtype=np.int8)
    p["fc.bias"] = rng.integers(-40, 40, cfg["n_classes"]).astype(np.int32)
    return p


def make_image(cfg, rng):
    return rng.integers(-8, 8, (cfg["res"], cfg["res"], cfg["in_channels"])
                        ).astype(np.int8)


def forward(cfg, p, shifts, x, operand_bits=None, calibrating=False):
    def shift(name, acc):
        if calibrating:
            std = max(1.0, float(np.std(acc)))
            shifts[name] = min(30, max(0, round(math.log2(std / TARGET_STD))))
        return shifts[name]

    t = {}
    acc = refcnn.conv_acc(x, p["conv1"], 1, 1, operand_bits)
    y = refcnn.relu(refcnn.requant(acc, shift("conv1", acc)))
    t["pool1"] = refcnn.maxpool(y, 2, 2, 0)
    acc = refcnn.conv_acc(t["pool1"], p["conv2"], 1, 1, operand_bits)

    def gap(s):
        y = refcnn.relu(refcnn.requant(acc, s))
        return refcnn.requant(refcnn.gap_sum(y), s,
                              div=y.shape[0] * y.shape[1])
    if calibrating:
        # the fused pool applies conv2's shift again: size it on the
        # pooled vector, so that the linear layer sees a signal
        shifts["conv2"] = min(range(31), key=lambda s: abs(math.log2(
            max(float(np.std(gap(s))), 1e-3) / TARGET_STD)))
    t["gap"] = gap(shifts["conv2"])
    acc = refcnn.linear_acc(t["gap"], p["fc"], np.zeros(cfg["n_classes"],
                                                         np.int64),
                            operand_bits)
    t["fc"] = refcnn.requant(acc + p["fc.bias"], shift("fc", acc))
    return t


def calibrate(cfg, p, x):
    shifts = {}
    forward(cfg, p, shifts, x, calibrating=True)
    return shifts
