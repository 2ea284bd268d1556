"""Plain integer reference of a quantized CNN forward pass.

Independent of the code under test: it imports nothing of ``repro``.
The arithmetic is the INT8 contract of a digital CIM chip:

* activations are HWC int8 maps; a convolution is an im2col matmul with
  ``(ky, kx, c)`` patch order and zero padding, accumulated exactly
  (int8 x int8 products summed in float64 are exact far beyond the
  ``K * 127 * 127`` a layer can reach, and BLAS makes that fast);
* requantization is ``clip((acc + den // 2) // den, -128, 127)`` with
  ``den = div << shift`` (``div`` folds a global average pool's mean);
* relu commutes with requantization, so it may sit on either side;
* a residual add saturates in int8; max-pool takes the maximum of the
  window's in-range positions, starting from zero (its input is
  post-relu);
* a linear layer adds an int32 bias to its accumulator.

``operand_bits=4`` rounds both operands of every matmul to a
per-tensor 4-bit grid first: the lower precision a comparison with this
reference has to reject.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

__all__ = ["conv_acc", "linear_acc", "requant", "sat_add", "relu",
           "maxpool", "gap_sum", "round_to_bits"]


def round_to_bits(x: np.ndarray, bits: int) -> np.ndarray:
    """``x`` on a symmetric per-tensor grid of ``bits`` bits (float64)."""
    x = np.asarray(x, dtype=np.float64)
    qmax = (1 << (bits - 1)) - 1
    scale = max(1.0, float(np.abs(x).max()) / qmax)
    return np.clip(np.round(x / scale), -qmax - 1, qmax) * scale


def _matmul(a: np.ndarray, b: np.ndarray,
            operand_bits: Optional[int]) -> np.ndarray:
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    if operand_bits is not None:
        a, b = round_to_bits(a, operand_bits), round_to_bits(b, operand_bits)
    return np.rint(a @ b).astype(np.int64)


def _im2col(x: np.ndarray, k: int, stride: int, pad: int) -> np.ndarray:
    h, w, c = x.shape
    xp = np.zeros((h + 2 * pad, w + 2 * pad, c), dtype=x.dtype)
    xp[pad:pad + h, pad:pad + w] = x
    ho = (h + 2 * pad - k) // stride + 1
    wo = (w + 2 * pad - k) // stride + 1
    win = np.lib.stride_tricks.sliding_window_view(xp, (k, k), axis=(0, 1))
    win = win[:ho * stride:stride, :wo * stride:stride]  # (ho, wo, c, k, k)
    return win.transpose(0, 1, 3, 4, 2).reshape(ho * wo, k * k * c), ho, wo


def conv_acc(x: np.ndarray, kernel: np.ndarray, stride: int, pad: int,
             operand_bits: Optional[int] = None) -> np.ndarray:
    """(H, W, Cin) int8 map, (k, k, Cin, Cout) int8 kernel ->
    (Ho, Wo, Cout) int64 accumulator."""
    k, _, cin, cout = kernel.shape
    cols, ho, wo = _im2col(x, k, stride, pad)
    acc = _matmul(cols, kernel.reshape(k * k * cin, cout), operand_bits)
    return acc.reshape(ho, wo, cout)


def linear_acc(x: np.ndarray, w: np.ndarray, bias: np.ndarray,
               operand_bits: Optional[int] = None) -> np.ndarray:
    """(K,) int8 vector, (K, N) int8 matrix, (N,) int32 bias -> (N,)."""
    return _matmul(x.reshape(1, -1), w, operand_bits)[0] + bias


def requant(acc: np.ndarray, shift: int, div: int = 1) -> np.ndarray:
    den = div << shift
    v = (np.asarray(acc, dtype=np.int64) + (den >> 1)) // den
    return np.clip(v, -128, 127).astype(np.int8)


def sat_add(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.clip(a.astype(np.int16) + b.astype(np.int16),
                   -128, 127).astype(np.int8)


def relu(x: np.ndarray) -> np.ndarray:
    return np.maximum(x, 0)


def maxpool(x: np.ndarray, k: int, stride: int, pad: int) -> np.ndarray:
    h, w, c = x.shape
    ho = (h + 2 * pad - k) // stride + 1
    wo = (w + 2 * pad - k) // stride + 1
    xp = np.zeros((h + 2 * pad, w + 2 * pad, c), dtype=x.dtype)
    xp[pad:pad + h, pad:pad + w] = x
    out = np.zeros((ho, wo, c), dtype=x.dtype)
    for dy in range(k):
        for dx in range(k):
            out = np.maximum(out, xp[dy:dy + ho * stride:stride,
                                     dx:dx + wo * stride:stride])
    return out


def gap_sum(x: np.ndarray) -> np.ndarray:
    """Channel sums of an (H, W, C) int8 map, and the window size."""
    return x.reshape(-1, x.shape[-1]).astype(np.int64).sum(axis=0)
