"""The numpy oracle's default contraction (``ref.exact_matmul``): a float64
BLAS GEMM bit-equal to numpy's int32 ``@``, its wraparound at K >= 2**17
included, and the ``repro.ref.oracle`` span's count of its calls."""

import numpy as np
import pytest

from repro.core import ref, workloads
from repro.core.arch import default_chip
from repro.core.graph import Graph
from repro.faults import FaultModel, resolve_faults

# resnet18@224's 21 contractions as (M, K, N), in graph order (1.814 GMAC)
RESNET18_224 = [
    (12544, 147, 64), (3136, 576, 64), (3136, 576, 64), (3136, 576, 64),
    (3136, 576, 64), (784, 576, 128), (784, 1152, 128), (784, 64, 128),
    (784, 1152, 128), (784, 1152, 128), (196, 1152, 256),
    (196, 2304, 256), (196, 128, 256), (196, 2304, 256),
    (196, 2304, 256), (49, 2304, 512), (49, 4608, 512), (49, 256, 512),
    (49, 4608, 512), (49, 4608, 512), (1, 512, 1000)]
# M cut to keep the integer reference fast; K and N as published
M_CUT = 64


def _operands(m, k, n, seed):
    """int8-ranged int32 operands: seeded, or all -128 for ``seed=None``."""
    if seed is None:
        return (np.full((m, k), -128, np.int32),
                np.full((k, n), -128, np.int32))
    rng = np.random.default_rng(seed)
    return (rng.integers(-128, 128, (m, k)).astype(np.int32),
            rng.integers(-128, 128, (k, n)).astype(np.int32))


CASES = ([pytest.param((min(m, M_CUT), k, n), i, False,
                       id=f"resnet18-224-{i}-{k}x{n}")
          for i, (m, k, n) in enumerate(RESNET18_224)]
         + [pytest.param((4, 4608, 3), None, False, id="all-min-k4608"),
            pytest.param((1, (1 << 17) + 1, 1), None, True,
                         id="int-path-wraps-k131073")])


@pytest.mark.parametrize("shape, seed, wraps", CASES)
def test_exact_matmul_is_bit_equal_to_int32_matmul(shape, seed, wraps):
    a, b = _operands(*shape, seed)
    want = a @ b
    got = ref.exact_matmul(a, b)
    assert got.dtype == want.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    tally = ref.ContractionTally()
    np.testing.assert_array_equal(tally(a, b), want)
    assert tally.f64 == 1
    if wraps:
        # the case is there for its wraparound: int32 ``@`` overflowed
        true = a.astype(np.int64) @ b.astype(np.int64)
        assert not np.array_equal(want.astype(np.int64), true)


def _wide_linear():
    """A K = 2**17 linear feeding a K = 4 one."""
    g = Graph("wide")
    x = g.input("x", (1 << 17,))
    g.linear("fc1", x, cout=4)
    g.linear("fc2", len(g.ops) - 1, cout=2)
    return g


@pytest.mark.parametrize("graph, batch, n_mvm", [
    pytest.param(lambda: workloads.tiny_cnn(res=8, c=8), 2, None,
                 id="tiny_cnn"),
    pytest.param(_wide_linear, 1, 2, id="wide-linear")])
def test_oracle_counts_every_contraction_by_path(graph, batch, n_mvm):
    cg = graph().condense()
    weights, biases, inputs = ref.random_init(cg, batch=batch, seed=3)
    quant = ref.auto_quant(cg, weights, biases, inputs)
    tally = ref.ContractionTally()
    got = ref.run_reference(cg, weights, biases, quant, inputs,
                            matmul=tally)
    anchored = sum(g.anchor is not None for g in cg)
    assert n_mvm is None or anchored == n_mvm
    assert tally.f64 == batch * anchored
    # the counting contraction is the default one
    want = ref.run_reference(cg, weights, biases, quant, inputs)
    assert got.keys() == want.keys()
    for gid in want:
        np.testing.assert_array_equal(got[gid], want[gid])


@pytest.mark.parametrize("model", [
    pytest.param(FaultModel(rate=5e-3, seed=1), id="stuck-at"),
    pytest.param(FaultModel(transient_rate=1e-3, seed=7), id="transient")])
def test_faulty_oracle_matches_int32_matmul(model):
    """Stuck-at weights and accumulator bit flips go through the default
    contraction exactly as through numpy's int32 ``@``."""
    cg = workloads.tiny_cnn(res=8, c=8).condense()
    weights, biases, inputs = ref.random_init(cg, batch=2, seed=3)
    quant = ref.auto_quant(cg, weights, biases, inputs)
    fs = resolve_faults(weights, default_chip(), model)
    clean = ref.run_reference(cg, weights, biases, quant, inputs)
    got = ref.run_reference(cg, weights, biases, quant, inputs, faults=fs)
    want = ref.run_reference(cg, weights, biases, quant, inputs,
                             matmul=lambda a, b: a @ b, faults=fs)
    assert any(not np.array_equal(got[g], clean[g]) for g in got)
    for gid in want:
        np.testing.assert_array_equal(got[gid], want[gid])
