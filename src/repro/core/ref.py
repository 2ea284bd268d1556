"""Reference INT8 oracle for compiled CIMFlow programs.

Pure-numpy forward pass with *bit-exact* semantics matching the code
generator + functional ISS contract:

* HWC activations, ``(ky, kx, c)`` im2col patch ordering
  (``(g, ky, kx)`` block-diagonal for depth-wise);
* INT32 accumulation, int32 bias, relu pre-quant (unless a residual
  add/scale follows — then int8 post-add);
* fixed-point requant ``clip((acc*scale + den/2) // den)`` with
  ``den = div << shift`` (``div`` folds the GAP mean);
* max-pool on int8 with zero-init windows (valid post-relu);
* saturating int8 residual adds / SE channel scaling;
* dynamic-weight matmuls (attention): the weight matrix is built from
  the weight-producer group's activations via
  :func:`repro.core.vecsem.dynamic_weight_matrix` — the same layout
  codegen's gather V_MOVs realize;
* fused ``softmax`` / ``layernorm`` / ``gelu`` through the shared
  integer semantics in :mod:`repro.core.vecsem`.

Also provides the weight-matrix builders tests use to generate gmem
images (`conv_weight_matrix`, `dwconv_weight_matrix`).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from . import vecsem
from .codegen import QuantParams, _main_and_skip_preds, _weight_pred
from .graph import CondensedGraph, Graph
from .oplevel import Im2colSpec

__all__ = ["conv_weight_matrix", "dwconv_weight_matrix", "im2col",
           "quantize", "run_reference", "auto_quant", "random_init",
           "exact_matmul", "ContractionTally"]

# the INT8 x INT8 -> INT32 accumulator contraction; swappable so the
# same oracle can execute its MVMs on an accelerator kernel (see
# ``flow.backends.PallasFuncBackend``) while everything around the
# matmul stays pure numpy.  Operand values always fit int8, so each
# product is at most 2**14 in magnitude and a K-term sum at most
# K * 2**14: exact in float64 for any K below 2**39.  The default
# (:func:`exact_matmul`) therefore runs the contraction as a float64
# BLAS GEMM and returns exactly the integers numpy's int32 ``@`` would.
MatmulFn = Callable[[np.ndarray, np.ndarray], np.ndarray]


def exact_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``(M, K) x (K, N) -> (M, N)`` int32, bit-equal to int32 ``a @ b``.

    Operand values fit int8 (the :data:`MatmulFn` contract), so every
    product is at most 2**14 in magnitude and the sum of K of them at
    most K * 2**14, exact in float64 (whose integers are exact up to
    2**53) for any K below 2**39.  So casting both operands to float64,
    multiplying with BLAS and casting back through int64 gives the true
    sum modulo 2**32: exactly the integers numpy's int32 ``@`` gives
    (which has no BLAS and runs a scalar loop), its wraparound at
    K >= 2**17 included.
    """
    return (a.astype(np.float64) @ b.astype(np.float64)
            ).astype(np.int64).astype(np.int32)


class ContractionTally:
    """:func:`exact_matmul` that counts its calls: the ``f64`` counter
    of the ``repro.ref.oracle`` span."""

    __slots__ = ("f64",)

    def __init__(self) -> None:
        self.f64 = 0

    def __call__(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        self.f64 += 1
        return exact_matmul(a, b)


def conv_weight_matrix(kernel: np.ndarray) -> np.ndarray:
    """(kh, kw, cin, cout) int8 kernel -> (kh*kw*cin, cout) matrix."""
    kh, kw, cin, cout = kernel.shape
    return kernel.reshape(kh * kw * cin, cout).astype(np.int8)


def dwconv_weight_matrix(kernel: np.ndarray) -> np.ndarray:
    """(kh, kw, C) depth-wise kernel -> block-diagonal (C*kh*kw, C)."""
    kh, kw, c = kernel.shape
    w = np.zeros((c * kh * kw, c), dtype=np.int8)
    for g in range(c):
        w[g * kh * kw:(g + 1) * kh * kw, g] = \
            kernel[:, :, g].reshape(-1)
    return w


def im2col(x: np.ndarray, kh: int, kw: int, stride: int, pad: int,
           depthwise: bool = False) -> np.ndarray:
    """HWC int8 map -> (ho*wo, K) patches; zero padding."""
    h, w, c = x.shape
    ho = (h + 2 * pad - kh) // stride + 1
    wo = (w + 2 * pad - kw) // stride + 1
    xp = np.zeros((h + 2 * pad, w + 2 * pad, c), dtype=x.dtype)
    xp[pad:pad + h, pad:pad + w] = x
    out = np.zeros((ho * wo, kh * kw * c), dtype=x.dtype)
    for y in range(ho):
        for xx in range(wo):
            patch = xp[y * stride:y * stride + kh,
                       xx * stride:xx * stride + kw]   # (kh, kw, c)
            if depthwise:
                # (g, ky, kx) ordering
                out[y * wo + xx] = patch.transpose(2, 0, 1).reshape(-1)
            else:
                out[y * wo + xx] = patch.reshape(-1)
    return out


def quantize(acc: np.ndarray, q: QuantParams, div: int = 1) -> np.ndarray:
    den = div << q.shift
    v = (acc.astype(np.int64) * q.scale + (den >> 1)) // den
    return np.clip(v, -128, 127).astype(np.int8)


def _sat_add(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.clip(a.astype(np.int16) + b.astype(np.int16),
                   -128, 127).astype(np.int8)


def _sat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.clip(a.astype(np.int32) * b.astype(np.int32),
                   -128, 127).astype(np.int8)


def _group_spec(cg: CondensedGraph, g) -> Optional[Tuple]:
    src = cg.source
    if src is None or g.anchor is None:
        return None
    op = src.ops[g.anchor]
    if op.kind not in ("conv", "dwconv"):
        return None
    h, w, cin = src.ops[op.inputs[0]].out_shape
    return (op.attrs["k"], op.attrs["stride"], op.attrs["padding"],
            op.kind == "dwconv")


def run_reference(cg: CondensedGraph, weights: Dict[int, np.ndarray],
                  biases: Dict[int, np.ndarray],
                  quant: Dict[int, QuantParams],
                  inputs: np.ndarray,
                  return_acc: bool = False,
                  matmul: Optional[MatmulFn] = None,
                  faults: Optional[Any] = None
                  ) -> Dict[int, np.ndarray]:
    """Forward-pass every sample; returns {gid: (batch, ...) int8 maps}
    (conv groups: (B, ho', wo', N) post-fusion; vector groups: (B, N)).

    ``matmul`` overrides the accumulator contraction
    ``(M, K) int32 x (K, N) int32 -> (M, N) int32`` (operand *values*
    always fit int8); the default is :func:`exact_matmul`, bit-equal
    to numpy's int32 ``@``.

    ``faults`` is an optional :class:`repro.faults.FaultSet`: static
    weight matrices are stuck-at-corrupted before the contraction and
    the int32 accumulator takes deterministic per-``(group, sample)``
    transient flips after it.  ``None`` (and an empty set) leave the
    oracle bit-exactly unchanged.  Dynamic-weight (attention) matmuls
    build their matrix from activations at run time and carry no
    stored-weight faults.
    """
    mm: MatmulFn = matmul if matmul is not None else exact_matmul
    src = cg.source
    assert src is not None, "reference needs the source graph"
    op_owner = {}
    for g in cg:
        for i in g.op_ids:
            op_owner[i] = g.idx
    B = inputs.shape[0]
    outs: Dict[int, np.ndarray] = {}
    accs: Dict[int, np.ndarray] = {}

    for g in cg:
        main, side = _main_and_skip_preds(cg, g, op_owner)
        wp = _weight_pred(cg, g, op_owner)
        spec = _group_spec(cg, g)
        q = quant[g.idx]
        res = []
        acc_dbg = []
        vops = _vops(cg, g)
        anchor_op = src.ops[g.anchor] if g.anchor is not None else None
        for s in range(B):
            x = inputs[s] if main is None else outs[main][s]
            if g.dynamic_weights:
                wbuf = inputs[s] if wp is None else outs[wp][s]
                W = vecsem.dynamic_weight_matrix(
                    wbuf, anchor_op.gemm_k, anchor_op.gemm_n,
                    anchor_op.groups,
                    bool(anchor_op.attrs.get("transpose_weights"))
                ).astype(np.int32)
            else:
                W = weights[g.idx]
                if faults is not None:
                    W = faults.corrupt_weight_matrix(g.idx, W)
                W = W.astype(np.int32)
            if spec is not None:
                k, stride, pad, dw = spec
                patches = im2col(x, k, k, stride, pad, dw).astype(np.int32)
                acc = mm(patches, W)
                anchor_op = src.ops[g.anchor]
                ho, wo, n = anchor_op.out_shape
            else:
                acc = mm(x.reshape(-1, W.shape[0]).astype(np.int32), W)
                ho, wo, n = 1, 1, W.shape[1]
            if faults is not None:
                acc = faults.corrupt_acc(acc, g.idx, s)
            acc_dbg.append(acc.copy())
            sv = (outs[side[0]][s] if side
                  else (inputs[s] if main is None else outs[main][s])) \
                if ("add" in vops or "mul" in vops) else None
            # process fused ops strictly in graph order
            i32 = True                    # still in the INT32 accumulator?
            y = None

            def leave_i32():
                nonlocal i32, y
                if i32:
                    z = quantize(acc, q)
                    y = (z.reshape(ho, wo, n) if spec is not None
                         else z.reshape(-1))
                    i32 = False

            for op in vops:
                if op == "bias":
                    acc = acc + biases[g.idx].astype(np.int32)[None, :]
                elif op == "relu":
                    if i32:
                        acc = np.maximum(acc, 0)
                    else:
                        y = np.maximum(y, 0)
                elif op in ("add", "mul"):
                    leave_i32()
                    if op == "mul":
                        y = _sat_mul(y, sv.reshape(
                            (1,) * (y.ndim - 1) + (-1,)))
                    else:
                        y = _sat_add(y, sv.reshape(y.shape))
                elif op == "maxpool":
                    leave_i32()
                    pk, ps, pp, pho, pwo = _pool_of(cg, g)
                    out = np.zeros((pho, pwo, n), dtype=np.int8)
                    for py in range(pho):
                        for px in range(pwo):
                            for jy in range(pk):
                                for jx in range(pk):
                                    iy = py * ps - pp + jy
                                    ix = px * ps - pp + jx
                                    if 0 <= iy < y.shape[0] and \
                                            0 <= ix < y.shape[1]:
                                        out[py, px] = np.maximum(
                                            out[py, px], y[iy, ix])
                    y = out
                elif op == "globalpool":
                    leave_i32()
                    m = y.reshape(-1, n)
                    tot = m.astype(np.int32).sum(axis=0)
                    y = quantize(tot, q, div=m.shape[0])
                elif op == "softmax":
                    # per head-row segment, matching codegen's VLEN
                    leave_i32()
                    seg = anchor_op.gemm_n if anchor_op is not None \
                        else y.shape[-1]
                    shp = y.shape
                    y = vecsem.softmax_i8(y.reshape(-1, seg)).reshape(shp)
                elif op == "layernorm":
                    leave_i32()
                    row = y.shape[-1]
                    if anchor_op is not None:
                        row = anchor_op.gemm_n * (
                            anchor_op.groups if anchor_op.groups > 1
                            else 1)
                    shp = y.shape
                    y = vecsem.layernorm_i8(
                        y.reshape(-1, row)).reshape(shp)
                elif op == "gelu":
                    leave_i32()
                    y = vecsem.gelu_i8(y)
                else:
                    raise NotImplementedError(
                        f"oracle: fused op {op!r} unsupported")
            leave_i32()
            res.append(y)
        outs[g.idx] = np.stack(res)
        if return_acc:
            accs[g.idx] = np.stack(acc_dbg)
    if return_acc:
        outs["acc"] = accs          # type: ignore[assignment]
    return outs


def _vops(cg: CondensedGraph, g) -> Tuple[str, ...]:
    src = cg.source
    out = []
    for i in g.op_ids:
        op = src.ops[i]
        if op.is_mvm or op.kind in ("bn", "flatten", "identity"):
            continue
        out.append(op.kind)
    return tuple(out)


def _pool_of(cg: CondensedGraph, g):
    src = cg.source
    for i in g.op_ids:
        op = src.ops[i]
        if op.kind == "maxpool":
            ho, wo, _ = op.out_shape
            return (op.attrs["k"], op.attrs["stride"],
                    op.attrs.get("padding", 0), ho, wo)
    return None


def _gap_of(cg: CondensedGraph, g) -> bool:
    src = cg.source
    return any(src.ops[i].kind == "globalpool" for i in g.op_ids)


def random_init(cg: CondensedGraph, batch: int = 1, seed: int = 0
                ) -> Tuple[Dict[int, np.ndarray],
                           Dict[int, np.ndarray], np.ndarray]:
    """Random int8 ``(weights, biases, inputs)`` for a condensed graph.

    Weights land in the ``(K_total, N_total)`` matrix layout codegen
    loads (conv kernels through :func:`conv_weight_matrix`, depth-wise
    through :func:`dwconv_weight_matrix`); values stay small so a few
    fused layers don't saturate before :func:`auto_quant` picks shifts.
    """
    src = cg.source
    assert src is not None, "random_init needs the source graph"
    rng = np.random.default_rng(seed)
    weights: Dict[int, np.ndarray] = {}
    biases: Dict[int, np.ndarray] = {}
    lo, hi = -6, 7
    for g in cg:
        if g.anchor is None:
            continue
        op = src.ops[g.anchor]
        if op.kind == "conv":
            k = op.attrs["k"]
            cin = src.ops[op.inputs[0]].out_shape[-1]
            ker = rng.integers(lo, hi, (k, k, cin, op.gemm_n),
                               dtype=np.int8)
            weights[g.idx] = conv_weight_matrix(ker)
        elif op.kind == "dwconv":
            k = op.attrs["k"]
            ker = rng.integers(lo, hi, (k, k, op.groups), dtype=np.int8)
            weights[g.idx] = dwconv_weight_matrix(ker)
        elif op.kind == "linear" and not g.dynamic_weights:
            weights[g.idx] = rng.integers(lo, hi, (g.gemm_k, g.gemm_n),
                                          dtype=np.int8)
        if "bias" in _vops(cg, g):
            biases[g.idx] = rng.integers(
                -40, 40, g.gemm_n * (g.groups if g.groups > 1 else 1)
            ).astype(np.int32)
    inputs = rng.integers(-8, 8, (batch,) + src.ops[0].out_shape
                          ).astype(np.int8)
    return weights, biases, inputs


def auto_quant(cg: CondensedGraph, weights: Dict[int, np.ndarray],
               biases: Dict[int, np.ndarray],
               inputs: np.ndarray) -> Dict[int, QuantParams]:
    """Pick per-group shifts that keep outputs in a healthy int8 range
    (fixed-point iteration of the oracle: downstream ranges depend on
    upstream quantization)."""
    qp = {g.idx: QuantParams(scale=1, shift=0) for g in cg}
    for _ in range(3):
        outs = run_reference(cg, weights, biases, qp, inputs,
                             return_acc=True)
        accs = outs["acc"]          # type: ignore[index]
        new = {}
        for g in cg:
            peak = max(1, int(np.abs(accs[g.idx]).max()))
            shift = (max(0, math.ceil(math.log2(peak / 100)))
                     if peak > 100 else 0)
            new[g.idx] = QuantParams(scale=1, shift=min(shift, 30))
        if new == qp:
            break
        qp = new
    return qp
