"""Jit'd public wrappers around the CIM kernels.

* :func:`cim_mvm` — bit-serial Pallas kernel with automatic zero-padding
  to MXU-aligned blocks (exact for integer arithmetic).
* :func:`int8_matmul` — the direct single-pass INT8 MXU path (the
  *performance* path; bit-identical to :func:`cim_mvm`).
* :func:`quantized_linear` — float-in/float-out linear with INT8 CIM
  arithmetic inside and a straight-through-estimator custom VJP, used by
  the framework's quantization-aware training / INT8 serving path.

Off a TPU the Pallas kernel runs in ``interpret=True``; on a TPU it
compiles natively.  ``interpret=None`` picks from the backend alone.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..compile_cache import enable_compile_cache
from .bitserial_mvm import bitserial_mvm_pallas
from .ref import mvm_ref

__all__ = ["cim_mvm", "int8_matmul", "quantized_linear", "pad_to"]


@functools.lru_cache(maxsize=None)
def _auto_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: on every backend
    but the TPU.  Memoized — ``jax.default_backend()`` initializes the
    platform backend, which is milliseconds per call."""
    return jax.default_backend() != "tpu"


def pad_to(a: jax.Array, mults) -> jax.Array:
    """Zero-pad each dim of ``a`` up to a multiple of ``mults``."""
    pads = []
    for dim, mult in zip(a.shape, mults):
        rem = (-dim) % mult
        pads.append((0, rem))
    if any(p[1] for p in pads):
        a = jnp.pad(a, pads)
    return a


@functools.partial(jax.jit, static_argnames=("act_bits", "block_m",
                                             "block_n", "block_k",
                                             "signed", "interpret"))
def cim_mvm(x: jax.Array, w: jax.Array, *, act_bits: int = 8,
            block_m: int = 128, block_n: int = 128, block_k: int = 128,
            signed: bool = True,
            interpret: Optional[bool] = None) -> jax.Array:
    """Bit-serial CIM MVM, ragged shapes welcome: int8 x int8 -> int32."""
    enable_compile_cache()               # traced before each compile
    if interpret is None:
        interpret = _auto_interpret()
    m, k = x.shape
    _, n = w.shape
    xp = pad_to(x.astype(jnp.int8), (block_m, block_k))
    wp = pad_to(w.astype(jnp.int8), (block_k, block_n))
    out = bitserial_mvm_pallas(xp, wp, act_bits=act_bits, block_m=block_m,
                               block_n=block_n, block_k=block_k,
                               signed=signed, interpret=interpret)
    return out[:m, :n]


@jax.jit
def int8_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """Direct INT8 MXU matmul (performance path, bit-identical)."""
    return mvm_ref(x.astype(jnp.int8), w.astype(jnp.int8))


# ---------------------------------------------------------------------------
# Fake-quant linear with straight-through estimator
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def quantized_linear(x: jax.Array, w_int8: jax.Array, scales,
                     use_pallas: bool = False) -> jax.Array:
    """``y = dequant(int8(x) @ w_int8)``; float32 in/out.

    ``scales = (act_scale, w_scale)`` — per-tensor symmetric.  Backward is
    the straight-through estimator on a dequantized weight view, so the
    op drops into a standard training loop.
    """
    act_scale, w_scale = scales
    xq = jnp.clip(jnp.round(x / act_scale), -128, 127).astype(jnp.int8)
    if use_pallas:
        acc = cim_mvm(xq, w_int8)
    else:
        acc = int8_matmul(xq, w_int8)
    return acc.astype(jnp.float32) * (act_scale * w_scale)


def _ql_fwd(x, w_int8, scales, use_pallas):
    y = quantized_linear(x, w_int8, scales, use_pallas)
    return y, (x, w_int8, scales)


def _ql_bwd(use_pallas, res, g):
    x, w_int8, (act_scale, w_scale) = res
    w_deq = w_int8.astype(jnp.float32) * w_scale
    # straight-through: d/dx ignores the quantizer's staircase
    dx = g @ w_deq.T
    dw = x.T @ g / w_scale          # gradient w.r.t. the int8 weight view
    return dx, dw, (jnp.zeros(()), jnp.zeros(()))


quantized_linear.defvjp(_ql_fwd, _ql_bwd)
