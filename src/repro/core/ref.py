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
  integer semantics in :mod:`repro.core.vecsem`;
* groups on codegen's ordered int8 tail (:func:`~repro.core.codegen.
  ordered_tail`: RMSNorm with its per-channel gain, RoPE, the gated
  product, SiLU/sigmoid) apply their fused ops in graph order, keep the
  stream's *tap* where another group reads it, and run input-side norms
  on the main operand;
* loop-shared groups read the weights and quantization of their owner
  (``Group.param_key``); an RMSNorm's gain γ is keyed by its owner op.

Also provides the weight-matrix builders tests use to generate gmem
images (`conv_weight_matrix`, `dwconv_weight_matrix`).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from . import vecsem
from .codegen import (QuantParams, _main_and_skip_preds, _main_operand_op,
                      _tail_ops, _weight_pred, causal_softmax, ordered_tail,
                      pre_ops)
from .graph import CondensedGraph, Graph
from .oplevel import Im2colSpec

__all__ = ["conv_weight_matrix", "dwconv_weight_matrix", "im2col",
           "quantize", "run_reference", "auto_quant", "random_init",
           "exact_matmul", "ContractionTally", "random_norms"]

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


def random_norms(cg: CondensedGraph, seed: int = 0
                 ) -> Dict[int, np.ndarray]:
    """Random int8 RMSNorm gains in {1, 2}, one per owning op
    (``Op.param_owner``)."""
    rng = np.random.default_rng(seed)
    keys = sorted({op.param_owner for op in cg.source.ops
                   if op.kind == "rmsnorm"})
    return {k: rng.integers(1, 3, cg.source.ops[k].out_shape[-1]
                            ).astype(np.int8) for k in keys}


def _gain(norms: Optional[Dict[int, np.ndarray]], op) -> np.ndarray:
    if norms is None:
        return np.ones(op.out_shape[-1], dtype=np.int8)
    return norms[op.param_owner]


def _apply_norm(x: np.ndarray, op, norms) -> np.ndarray:
    """RMSNorm over each row, then the saturating int8 gain γ."""
    n = op.out_shape[-1]
    y = vecsem.rmsnorm_i8(x.reshape(-1, n))
    return _sat_mul(y, _gain(norms, op)[None, :]).reshape(x.shape)


def _rope(y: np.ndarray, op) -> np.ndarray:
    """RoPE on every head of (rows, d) int8; row index = position."""
    hd = op.attrs["head_dim"]
    rows = y.shape[0]
    tab = vecsem.rope_table(rows, hd, op.attrs["theta"])
    out = np.empty_like(y)
    for h0 in range(0, y.shape[1], hd):
        out[:, h0:h0 + hd] = vecsem.rope_i8(y[:, h0:h0 + hd], tab)
    return out


def run_reference(cg: CondensedGraph, weights: Dict[int, np.ndarray],
                  biases: Dict[int, np.ndarray],
                  quant: Dict[int, QuantParams],
                  inputs: np.ndarray,
                  return_acc: bool = False,
                  matmul: Optional[MatmulFn] = None,
                  faults: Optional[Any] = None,
                  norms: Optional[Dict[int, np.ndarray]] = None
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

    ``weights``, ``biases`` and ``quant`` are keyed by
    ``Group.param_key`` (a loop-shared group reads its owner's);
    ``norms`` maps ``Op.param_owner`` to int8 RMSNorm gains (``None``:
    all ones).  With ``return_acc`` the result also holds ``"acc"``
    (per-group accumulators) and ``"glu"`` (per-group peak magnitude of
    the gated products before their requantization).
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
    taps: Dict[int, np.ndarray] = {}
    glu_peak: Dict[int, int] = {}

    def operand(i: int, s: int) -> np.ndarray:
        """Sample ``s`` of op ``i``'s output, read outside its group."""
        if src.ops[i].kind == "input":
            return inputs[s]
        gid = op_owner[i]
        return taps[gid][s] if cg[gid].tap == i and gid in taps \
            else outs[gid][s]

    for g in cg:
        main, side = _main_and_skip_preds(cg, g, op_owner)
        wp = _weight_pred(cg, g, op_owner)
        spec = _group_spec(cg, g)
        q = quant[g.param_key]
        if ordered_tail(cg, g):
            res, tap, acc_dbg, peak = [], [], [], 0
            for s in range(B):
                y, t, acc, p = _ordered_sample(
                    cg, g, weights, biases, q, norms, mm, faults, s,
                    lambda i: operand(i, s))
                res.append(y)
                tap.append(t)
                acc_dbg.append(acc)
                peak = max(peak, p)
            outs[g.idx] = np.stack(res)
            if g.tap is not None:
                taps[g.idx] = np.stack(tap)
            if return_acc:
                accs[g.idx] = np.stack(acc_dbg)
                glu_peak[g.idx] = peak
            continue
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
                W = weights[g.param_key]
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
                    y = _softmax(y, n, seg, causal_softmax(cg, g))
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
        outs["glu"] = glu_peak      # type: ignore[assignment]
    return outs


def _softmax(y: np.ndarray, n: int, seg: int, causal: bool) -> np.ndarray:
    """Softmax per head segment of ``seg`` on int8 rows of ``n``; causal:
    row ``r`` sees keys ``0..r`` (:func:`vecsem.causal_softmax_i8`)."""
    if causal:
        return vecsem.causal_softmax_i8(y.reshape(-1, n), seg).reshape(
            y.shape)
    return vecsem.softmax_i8(y.reshape(-1, seg)).reshape(y.shape)


def _ordered_sample(cg: CondensedGraph, g, weights, biases,
                    q: QuantParams, norms, mm: MatmulFn, faults, s: int,
                    operand: Callable[[int], np.ndarray]):
    """One sample of an ordered-tail group (a linear anchor): input-side
    norms, the INT32 contraction with bias and a leading relu, the
    requantization, then every fused op in graph order on int8 rows.
    Returns ``(output, tap or None, accumulator, peak |glu product|)``.
    """
    src = cg.source
    anchor = src.ops[g.anchor]
    x = operand(_main_operand_op(cg, g))
    for i in pre_ops(cg, g):
        x = _apply_norm(x.reshape(-1, src.ops[i].out_shape[-1]),
                        src.ops[i], norms)
    W = weights[g.param_key]
    if faults is not None:
        W = faults.corrupt_weight_matrix(g.idx, W)
    acc = mm(x.reshape(-1, W.shape[0]).astype(np.int32),
             W.astype(np.int32))
    if faults is not None:
        acc = faults.corrupt_acc(acc, g.idx, s)
    acc_dbg = acc.copy()
    head = list(_vops(cg, g))
    if head[:1] == ["bias"]:
        acc = acc + biases[g.param_key].astype(np.int32)[None, :]
        head = head[1:]
    if head[:1] == ["relu"]:
        acc = np.maximum(acc, 0)
    y = quantize(acc, q)
    tap, peak = None, 0
    for op in (src.ops[i] for i in _tail_ops(cg, g)):
        other = [i for i in op.inputs if i not in g.op_ids]
        if op.kind == "add":
            y = _sat_add(y, operand(other[0]).reshape(y.shape))
        elif op.kind == "glu":
            gate = operand(other[0]).reshape(y.shape)
            p = vecsem.silu_i8(gate).astype(np.int64) * y.astype(np.int64)
            peak = max(peak, int(np.abs(p).max(initial=0)))
            y = vecsem.glu_i8(gate, y, q.mul_shift)
        elif op.kind == "rmsnorm":
            y = _apply_norm(y, op, norms)
        elif op.kind == "rope":
            y = _rope(y, op)
        elif op.kind == "softmax":
            y = _softmax(y, y.shape[-1], anchor.gemm_n,
                         bool(op.attrs.get("causal")))
        elif op.kind == "layernorm":
            y = vecsem.layernorm_i8(y)
        elif op.kind in ("silu", "sigmoid", "gelu"):
            y = getattr(vecsem, f"{op.kind}_i8")(y)
        elif op.kind == "relu":
            y = np.maximum(y, 0)
        else:
            raise NotImplementedError(
                f"oracle: fused op {op.kind!r} unsupported on the "
                f"ordered tail")
        if op.idx == g.tap:
            tap = y.copy()
    return y.reshape(-1), tap, acc_dbg, peak


def _vops(cg: CondensedGraph, g) -> Tuple[str, ...]:
    src = cg.source
    out = []
    for i in g.op_ids:
        op = src.ops[i]
        if op.is_mvm or op.kind in ("bn", "flatten", "identity") \
                or op.attrs.get("pre"):
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
        if g.anchor is None or g.owner is not None:
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
               inputs: np.ndarray,
               norms: Optional[Dict[int, np.ndarray]] = None
               ) -> Dict[int, QuantParams]:
    """Pick per-group shifts that keep outputs in a healthy int8 range
    (fixed-point iteration of the oracle: downstream ranges depend on
    upstream quantization).  Loop-shared groups get one shift per owner,
    sized on the largest of their accumulators; a gated product's
    ``mul_shift`` is sized the same way on its products."""
    def shift_for(peak: int) -> int:
        return (min(max(0, math.ceil(math.log2(peak / 100))), 30)
                if peak > 100 else 0)

    keys = sorted({g.param_key for g in cg})
    qp = {k: QuantParams(scale=1, shift=0) for k in keys}
    for _ in range(3):
        outs = run_reference(cg, weights, biases, qp, inputs,
                             return_acc=True, norms=norms)
        accs = outs["acc"]          # type: ignore[index]
        glu = outs["glu"]           # type: ignore[index]
        peak: Dict[int, int] = {}
        mpeak: Dict[int, int] = {}
        for g in cg:
            k = g.param_key
            peak[k] = max(peak.get(k, 1),
                          int(np.abs(accs[g.idx]).max()))
            mpeak[k] = max(mpeak.get(k, 1), glu.get(g.idx, 1))
        new = {k: QuantParams(scale=1, shift=shift_for(peak[k]),
                              mul_shift=shift_for(mpeak[k]))
               for k in keys}
        if new == qp:
            break
        qp = new
    return qp
