"""Bit-serial digital-CIM MVM as a Pallas TPU kernel.

Hardware adaptation (DESIGN.md §2): a digital CIM macro computes
``y = Σ_b 2^b · (x_b · W)`` over *activation bit-planes* with shift-add
accumulation — multiplications decompose into bit-wise AND-popcount rows,
which is exactly a {0,1}-matrix multiply.  On TPU we express the same
arithmetic as ``act_bits`` MXU matmuls over bit-planes with INT32
shift-add accumulation, tiled for VMEM:

* grid ``(M/bm, N/bn, K/bk)`` — K innermost ("arbitrary" semantics), with
  an INT32 VMEM accumulator scratch carried across K steps;
* per step: slice the int8 activation tile, peel ``act_bits`` bit-planes
  (two's complement: the MSB plane enters negatively), one
  ``dot_general(plane_i8, w_i8) -> int32`` per plane on the MXU,
  shift-added into the accumulator;
* block shapes default to MXU-aligned multiples of 128 (the ``ops``
  wrapper zero-pads ragged shapes — exact for integer arithmetic).

This kernel is the *semantics* path: bit-exact with the CIMFlow
functional simulator's macro model and the pure-jnp oracle in
:mod:`repro.kernels.ref`.  The *performance* path (`int8_matmul` in
:mod:`repro.kernels.ops`) issues one direct int8 MXU matmul; both return
identical INT32 results, and the ratio of their costs (``act_bits`` : 1)
is precisely the bit-serial beat count the cycle-accurate simulator
charges per CIM pass.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bitserial_mvm_kernel", "bitserial_mvm_pallas"]


def bitserial_mvm_kernel(x_ref, w_ref, o_ref, acc_ref, *, act_bits: int,
                         k_steps: int, signed: bool) -> None:
    """One (bm, bn) output tile; accumulates over the K grid axis."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                # (bm, bk) int8
    w = w_ref[...]                                # (bk, bn) int8
    # two's-complement bit peel on the unsigned reinterpretation
    xu = x.astype(jnp.uint8).astype(jnp.int32)
    acc = acc_ref[...]
    for b in range(act_bits):
        plane = ((xu >> b) & 1).astype(jnp.int8)  # {0,1} bit-plane
        term = jax.lax.dot_general(
            plane, w,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        if signed and b == act_bits - 1:
            acc = acc - (term << b)               # MSB is negative
        else:
            acc = acc + (term << b)
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def bitserial_mvm_pallas(x: jax.Array, w: jax.Array, *, act_bits: int = 8,
                         block_m: int = 128, block_n: int = 128,
                         block_k: int = 128, signed: bool = True,
                         interpret: bool = False) -> jax.Array:
    """``(M, K) int8 @ (K, N) int8 -> (M, N) int32`` via bit-serial planes.

    Shapes must be multiples of the block sizes — use
    :func:`repro.kernels.ops.cim_mvm` for automatic padding.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"shape ({m},{k})x({k},{n}) not divisible by blocks "
        f"({block_m},{block_n},{block_k})")
    k_steps = k // block_k
    grid = (m // block_m, n // block_n, k_steps)
    kernel = functools.partial(bitserial_mvm_kernel, act_bits=act_bits,
                               k_steps=k_steps, signed=signed)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, s: (i, s)),
            pl.BlockSpec((block_k, block_n), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
