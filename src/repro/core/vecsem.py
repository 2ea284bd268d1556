"""Shared integer semantics for data-dependent vector ops.

The functional ISS (:mod:`repro.core.simulator`) and the numpy oracle
(:mod:`repro.core.ref`) must agree *bit-exactly* on every operation a
compiled program performs.  For relu / add / quant that contract is a
few lines of saturating int8 arithmetic; the transformer ops —
softmax, layernorm, gelu — need a fixed-point definition that both
sides share, so it lives here and is imported by both.

The definitions are LUT/shift arithmetic a digital CIM vector unit can
realize:

* ``softmax_i8``  — per row segment: ``e = EXP2_LUT[max(x) - x]``
  (Q14 table of ``2^(-d/16)``), output ``round(127·e / Σe)``;
  :func:`causal_softmax_i8` masks each query row to its keys ``<=`` its
  position (a decoder's attention), the masked entries reading 0;
* ``layernorm_i8`` — per row: n-scaled deviations ``d = n·x - Σx``,
  integer RMS via exact ``isqrt``, output ``round(G·d / rms)`` with
  gain ``G = 48`` (≈ 2.6σ of headroom in int8);
* ``gelu_i8``     — 256-entry LUT at 1/16-unit input scale;
* ``rmsnorm_i8``  — ``layernorm_i8`` without the mean removal (the
  per-channel gain γ is a separate saturating int8 multiply);
* ``silu_i8`` / ``sigmoid_i8`` — 256-entry LUTs at 1/16-unit input
  scale (sigmoid outputs ``round(127·σ)``);
* ``glu_i8``      — gated product ``silu(gate) ⊙ up``: int8 × int8 into
  an int32 accumulator, then the requantization shift of ``V_QUANT``;
* ``rope_i8``     — rotary positions on rotate-half pairs
  ``(i, i + hd/2)`` of each head, with Q14 cos/sin tables by position
  and channel (:func:`rope_table`).

Also provides :func:`dynamic_weight_matrix`, the one definition of how
a *dynamic* weight operand (a predecessor op's activations — see the
weight-source abstraction in :mod:`repro.core.graph`) maps onto the
block-diagonal ``(K_total, N_total)`` CIM layout.  Codegen's gather
V_MOVs, the functional ISS and the oracle all follow this layout.
"""

from __future__ import annotations

import numpy as np

__all__ = ["softmax_i8", "causal_softmax_i8", "layernorm_i8", "rmsnorm_i8",
           "gelu_i8", "silu_i8", "sigmoid_i8", "glu_i8", "rope_table",
           "rope_i8", "dynamic_weight_matrix", "EXP2_LUT", "GELU_LUT",
           "SILU_LUT", "SIGMOID_LUT", "LN_GAIN", "ROPE_FRAC"]

# EXP2_LUT[d] = round(2^14 · 2^(-d/16)) for d = max(x) - x in [0, 255]:
# a 16-th-of-a-unit exponent step keeps 8 input units of dynamic range.
EXP2_LUT = np.round(
    2.0 ** 14 * 2.0 ** (-np.arange(256, dtype=np.float64) / 16.0)
).astype(np.int64)

# GELU on int8 at 1/16-unit input scale: y = round(v · Φ(v/16))
# (tanh approximation), clipped to int8.
_v = np.arange(-128, 128, dtype=np.float64)
_t = _v / 16.0
_phi = 0.5 * (1.0 + np.tanh(0.7978845608028654
                            * (_t + 0.044715 * _t ** 3)))
GELU_LUT = np.clip(np.round(_v * _phi), -128, 127).astype(np.int8)
# SiLU at the same scale, y = round(v · σ(v/16)); sigmoid as a gate in
# [0, 1] at 1/127 output scale, y = round(127 · σ(v/16))
_sig = 1.0 / (1.0 + np.exp(-_t))
SILU_LUT = np.clip(np.round(_v * _sig), -128, 127).astype(np.int8)
SIGMOID_LUT = np.clip(np.round(127.0 * _sig), 0, 127).astype(np.int8)
del _v, _t, _phi, _sig

LN_GAIN = 48          # layernorm output scale (target std in int8 units)


def softmax_i8(x: np.ndarray, visible=None) -> np.ndarray:
    """Row-wise integer softmax: int8 ``(..., n)`` → int8 in [0, 127].

    ``visible`` (ints of shape ``x.shape[:-1]``, each at least 1): a
    row normalizes over its first ``visible`` elements only, and the
    others read 0 -- the softmax of that prefix, zero-filled."""
    xi = x.astype(np.int64)
    if visible is None:
        keep = True
        mx = xi.max(axis=-1, keepdims=True)
    else:
        keep = (np.arange(x.shape[-1])
                < np.asarray(visible, dtype=np.int64)[..., None])
        mx = np.where(keep, xi, -128).max(axis=-1, keepdims=True)
    d = np.clip(mx - xi, 0, 255)
    e = np.where(keep, EXP2_LUT[d], 0)
    s = e.sum(axis=-1, keepdims=True)
    y = (127 * e + (s >> 1)) // s
    return np.clip(y, 0, 127).astype(np.int8)


def causal_softmax_i8(x: np.ndarray, seg: int) -> np.ndarray:
    """Causal attention probabilities: int8 ``(rows, n)`` whose row
    ``r`` is query position ``r`` and holds ``n / seg`` head segments of
    ``seg`` keys; each segment normalizes over keys ``0..r``."""
    rows, n = x.shape
    vis = np.repeat(np.arange(1, rows + 1), n // seg)
    return softmax_i8(x.reshape(-1, seg), vis).reshape(rows, n)


def _isqrt(v: np.ndarray) -> np.ndarray:
    """Exact elementwise floor-sqrt of non-negative int64."""
    r = np.sqrt(v.astype(np.float64)).astype(np.int64)
    r = np.where(r * r > v, r - 1, r)            # float64 sqrt is within
    r = np.where((r + 1) * (r + 1) <= v, r + 1, r)   # ±1 ulp of exact
    return np.maximum(r, 0)


def layernorm_i8(x: np.ndarray, center: bool = True) -> np.ndarray:
    """Row-wise integer layernorm: int8 ``(..., n)`` → int8;
    ``center=False`` skips the mean removal (:func:`rmsnorm_i8`)."""
    xi = x.astype(np.int64)
    n = x.shape[-1]
    s = xi.sum(axis=-1, keepdims=True) if center else 0
    d = n * xi - s                               # n-scaled deviation
    ss = (d * d).sum(axis=-1, keepdims=True)
    r = _isqrt(ss // n) + 1                      # n-scaled RMS (+1: /0)
    y = (2 * LN_GAIN * d + r) // (2 * r)         # round-half-up
    return np.clip(y, -128, 127).astype(np.int8)


def rmsnorm_i8(x: np.ndarray) -> np.ndarray:
    """Row-wise integer RMSNorm: ``round(G·x / rms(x))`` in int8, the
    deviations of :func:`layernorm_i8` taken from 0 instead of the
    mean (the same unit, its centring off)."""
    return layernorm_i8(x, center=False)


def gelu_i8(x: np.ndarray) -> np.ndarray:
    """Elementwise int8 GELU through the shared LUT."""
    return GELU_LUT[x.astype(np.int16) + 128]


def silu_i8(x: np.ndarray) -> np.ndarray:
    """Elementwise int8 SiLU through the shared LUT."""
    return SILU_LUT[x.astype(np.int16) + 128]


def sigmoid_i8(x: np.ndarray) -> np.ndarray:
    """Elementwise int8 sigmoid through the shared LUT."""
    return SIGMOID_LUT[x.astype(np.int16) + 128]


def glu_i8(gate: np.ndarray, up: np.ndarray, shift: int) -> np.ndarray:
    """Gated product ``silu(gate) ⊙ up``: the int8 × int8 products
    accumulate in int32 and requantize with round-half-up by
    ``2**shift`` (``V_QUANT`` at scale 1)."""
    p = silu_i8(gate).astype(np.int64) * up.astype(np.int64)
    den = 1 << shift
    return np.clip((p + (den >> 1)) // den, -128, 127).astype(np.int8)


ROPE_FRAC = 14        # cos/sin table fraction bits (Q14)


def rope_table(n_pos: int, head_dim: int, theta: float) -> np.ndarray:
    """Q14 rotary table, int16 ``(n_pos, head_dim)``: row ``p`` holds
    ``round(2^14 cos(p·f_i))`` for ``i < hd/2``, then the sines, with
    ``f_i = theta^(-2i/hd)`` (the published RoPE frequencies)."""
    half = head_dim // 2
    f = float(theta) ** (-np.arange(half, dtype=np.float64) * 2.0
                         / head_dim)
    ang = np.arange(n_pos, dtype=np.float64)[:, None] * f[None, :]
    one = float(1 << ROPE_FRAC)
    return np.concatenate([np.round(one * np.cos(ang)),
                           np.round(one * np.sin(ang))],
                          axis=1).astype(np.int16)


def rope_i8(x: np.ndarray, table: np.ndarray) -> np.ndarray:
    """Rotary positions on one head segment: int8 ``(rows, hd)`` and
    its rows' table entries ``(rows, hd)`` → int8.  Pairs ``(i,
    i + hd/2)`` rotate by the row's angle:
    ``y_i = x_i·c − x_{i+h}·s``, ``y_{i+h} = x_{i+h}·c + x_i·s``,
    rounded half up from Q14 and saturated."""
    half = x.shape[-1] // 2
    xi = x.astype(np.int64)
    t = table.astype(np.int64)
    c, s = t[..., :half], t[..., half:]
    a, b = xi[..., :half], xi[..., half:]
    v = np.concatenate([a * c - b * s, b * c + a * s], axis=-1)
    r = (v + (1 << (ROPE_FRAC - 1))) >> ROPE_FRAC
    return np.clip(r, -128, 127).astype(np.int8)


def dynamic_weight_matrix(buf: np.ndarray, gemm_k: int, gemm_n: int,
                          groups: int, transpose: bool) -> np.ndarray:
    """Producer activations → block-diagonal ``(K_total, N_total)`` int8.

    ``buf`` is the weight producer's per-sample output in its natural
    row layout — ``(rows, groups·gemm_k)`` when ``transpose`` (Q·Kᵀ:
    rows are sequence positions, per-head channels become weight rows)
    or ``(gemm_k, groups·gemm_n)`` otherwise (P·V: rows are weight
    rows directly).
    """
    w = gemm_k if transpose else gemm_n
    b = np.asarray(buf).reshape(-1, groups * w)
    W = np.zeros((groups * gemm_k, groups * gemm_n), dtype=np.int8)
    for gi in range(groups):
        blk = b[:, gi * w:(gi + 1) * w]
        W[gi * gemm_k:(gi + 1) * gemm_k,
          gi * gemm_n:(gi + 1) * gemm_n] = blk.T if transpose else blk
    return W
