"""End-to-end: graph -> partition -> codegen -> functional ISS == oracle.

These are the paper-system behaviour tests: compiled CIMFlow instruction
streams executed by the functional simulator must be bit-exact against the
pure-numpy INT8 oracle, across single-core, multi-core (n-split assembly),
duplicated (weight replication) and multi-round (weight streaming) mappings.
"""

import numpy as np
import pytest

from repro.core import ref, workloads
from repro.core.arch import default_chip
from repro.core.codegen import CompiledModel, QuantParams, compile_model
from repro.core.graph import Graph
from repro.core.mapping import CostParams
from repro.core.partition import partition
from repro.core.simulator import Simulator

RNG = np.random.default_rng(0)


def _weights_for(cg):
    """Random int8 weights/biases in the (K_total, N_total) matrix layout."""
    src = cg.source
    weights, biases = {}, {}
    for g in cg:
        if g.anchor is None:
            continue
        op = src.ops[g.anchor]
        lo, hi = -6, 7
        if op.kind == "conv":
            k = op.attrs["k"]
            cin = src.ops[op.inputs[0]].out_shape[-1]
            ker = RNG.integers(lo, hi, (k, k, cin, op.gemm_n),
                               dtype=np.int8)
            weights[g.idx] = ref.conv_weight_matrix(ker)
        elif op.kind == "dwconv":
            k = op.attrs["k"]
            c = op.groups
            ker = RNG.integers(lo, hi, (k, k, c), dtype=np.int8)
            weights[g.idx] = ref.dwconv_weight_matrix(ker)
        elif op.kind == "linear":
            weights[g.idx] = RNG.integers(lo, hi, (g.gemm_k, g.gemm_n),
                                          dtype=np.int8)
        if "bias" in ref._vops(cg, g):
            biases[g.idx] = RNG.integers(-40, 40, g.gemm_n
                                         * (g.groups if g.groups > 1
                                            else 1)).astype(np.int32)
    return weights, biases


def _run_both(graph: Graph, chip, batch=2, strategy="dp", params=None):
    cg = graph.condense()
    res = partition(cg, chip, strategy,
                    params or CostParams(batch=batch))
    weights, biases = _weights_for(cg)
    inputs = RNG.integers(-8, 8, (batch,) + cg.source.ops[0].out_shape
                          ).astype(np.int8)
    qp = ref.auto_quant(cg, weights, biases, inputs)
    model = compile_model(res, batch=batch, quant=qp, strict_lmem=True)
    img = model.build_gmem_image(weights, biases, inputs)
    sim = Simulator(chip, model.isa, mode="func")
    rep = sim.run_model(model, gmem_image=img)
    oracle = ref.run_reference(cg, weights, biases, qp, inputs)
    return model, rep, oracle, cg


def _check_final(model: CompiledModel, rep, oracle, cg, batch=2):
    last = len(cg) - 1
    for s in range(batch):
        addr, nb = model.output_addr(last, s)
        got = rep.gmem[addr - 0x10000000: addr - 0x10000000 + nb]
        want = oracle[last][s].reshape(-1)
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"sample {s} mismatch")


# ---------------------------------------------------------------------------


def test_single_linear_layer():
    g = Graph("lin")
    x = g.input("x", (64,))
    g.linear("fc", x, cout=32, act="relu")
    chip = default_chip(n_cores=1, mesh_cols=1)
    model, rep, oracle, cg = _run_both(g, chip)
    _check_final(model, rep, oracle, cg)
    assert rep.cycles > 0 and rep.instrs > 0


def test_linear_multicore_nsplit():
    """N=256 on a 2-MG chip forces n-tile columns across 2+ cores
    (assembly-core gather path)."""
    g = Graph("lin2")
    x = g.input("x", (256,))
    g.linear("fc1", x, cout=256, act="relu")
    g2 = g.linear("fc2", len(g.ops) - 1, cout=16)
    chip = default_chip(n_cores=4, mesh_cols=2, n_macro_groups=2,
                        macros_per_group=2)
    model, rep, oracle, cg = _run_both(g, chip)
    _check_final(model, rep, oracle, cg)
    # verify the n-split actually happened
    sched = model.stages[0].schedules[0]
    assert len(sched.replicas[0].cores) >= 2


def test_linear_multiround_streaming():
    """K=4096 on a tiny CIM unit exceeds slots -> weight-streaming rounds."""
    g = Graph("big_k")
    x = g.input("x", (4096,))
    g.linear("fc", x, cout=8)
    chip = default_chip(n_cores=1, mesh_cols=1, n_macro_groups=4,
                        macros_per_group=1)
    model, rep, oracle, cg = _run_both(g, chip, batch=1)
    sched = model.stages[0].schedules[0]
    assert sched.n_rounds > 1
    _check_final(model, rep, oracle, cg, batch=1)


def test_tiny_cnn_end_to_end():
    """conv -> maxpool -> conv -> GAP -> fc across multiple cores."""
    g = workloads.tiny_cnn(res=8, c=8)
    chip = default_chip(n_cores=8, mesh_cols=4)
    model, rep, oracle, cg = _run_both(g, chip)
    _check_final(model, rep, oracle, cg)


def test_residual_block_skip_add():
    g = Graph("res")
    x = g.input("x", (8, 8, 8))
    c1 = g.conv("c1", x, cout=8, k=3, act="relu", use_bn=False)
    c2 = g.conv("c2", c1, cout=8, k=3, use_bn=False)
    a = g.eltwise("add", "add", c2, c1)
    r = g.unary("relu", "relu", a)
    g.linear("fc", g.globalpool("gap", r), cout=4)
    chip = default_chip(n_cores=8, mesh_cols=4)
    model, rep, oracle, cg = _run_both(g, chip)
    _check_final(model, rep, oracle, cg)


def test_depthwise_conv():
    g = Graph("dw")
    x = g.input("x", (8, 8, 16))
    d = g.conv("dw", x, cout=16, k=3, groups=16, act="relu", use_bn=False)
    g.linear("fc", g.globalpool("gap", d), cout=4)
    chip = default_chip(n_cores=4, mesh_cols=2)
    model, rep, oracle, cg = _run_both(g, chip)
    _check_final(model, rep, oracle, cg)


def test_strided_conv_with_padding():
    g = Graph("stride")
    x = g.input("x", (9, 9, 4))
    c = g.conv("c", x, cout=8, k=3, stride=2, act="relu", use_bn=False)
    g.linear("fc", g.globalpool("gap", c), cout=4)
    chip = default_chip(n_cores=4, mesh_cols=2)
    model, rep, oracle, cg = _run_both(g, chip)
    _check_final(model, rep, oracle, cg)


def test_duplication_correctness():
    """Plenty of cores -> optimal mapping duplicates; results unchanged."""
    g = Graph("dup")
    x = g.input("x", (12, 12, 4))
    c1 = g.conv("c1", x, cout=8, k=3, act="relu", use_bn=False)
    c2 = g.conv("c2", c1, cout=8, k=3, act="relu", use_bn=False)
    g.linear("fc", g.globalpool("gap", c2), cout=4)
    chip = default_chip(n_cores=16, mesh_cols=4)
    model, rep, oracle, cg = _run_both(g, chip, batch=2)
    dups = [s.alloc.dup for st in model.stages for s in st.schedules]
    assert max(dups) > 1, "expected weight duplication to kick in"
    _check_final(model, rep, oracle, cg)


def test_maxpool_with_padding():
    g = Graph("poolpad")
    x = g.input("x", (8, 8, 4))
    c = g.conv("c", x, cout=8, k=3, act="relu", use_bn=False)
    p = g.pool("p", c, k=3, stride=2, padding=1)
    g.linear("fc", g.globalpool("gap", p), cout=4)
    chip = default_chip(n_cores=4, mesh_cols=2)
    model, rep, oracle, cg = _run_both(g, chip)
    _check_final(model, rep, oracle, cg)


def test_streamed_group_shares_stage():
    """A weight-streaming (multi-round) group no longer monopolizes its
    stage: it co-schedules with its producer on disjoint core windows,
    pipelines within the stage, and stays bit-exact."""
    g = Graph("stream_shared")
    x = g.input("x", (32, 32, 4))
    c = g.conv("c1", x, cout=4, k=3, act="relu", use_bn=False)
    f = g.unary("flatten", "flatten", c)
    h, w, cc = g.ops[c].out_shape
    g.ops[f].out_shape = (h * w * cc,)
    g.linear("fc", f, cout=8)
    chip = default_chip(n_cores=2, mesh_cols=1, n_macro_groups=4,
                        macros_per_group=1)
    model, rep, oracle, cg = _run_both(g, chip, batch=2)
    assert len(model.stages) == 1, "streaming group not co-scheduled"
    by_src = {sc.weight_source: sc for st in model.stages
              for sc in st.schedules}
    assert by_src["streamed"].n_rounds > 1
    assert "static" in by_src
    _check_final(model, rep, oracle, cg)


def test_transformer_dynamic_weights_end_to_end():
    """Dynamic-weight attention (Q·Kᵀ / P·V written into macro groups
    from RECV'd activations) + fused softmax/layernorm/gelu tails: the
    compiled streams must match the oracle bit-exactly on the default
    chip, through the weight-source lowering path."""
    g = workloads.transformer_lm(n_layers=1, d_model=128, n_heads=4,
                                 seq=16, vocab=64)
    chip = default_chip()
    model, rep, oracle, cg = _run_both(g, chip, batch=2)
    _check_final(model, rep, oracle, cg)
    sources = {sc.weight_source for st in model.stages
               for sc in st.schedules}
    assert "dynamic" in sources, "attention did not lower dynamically"


def test_transformer_dynamic_multiround_end_to_end():
    """A slot-starved chip forces the dynamic path through multi-round
    streaming with multiple m-chunks — the restriction the static path
    still has — and must stay bit-exact."""
    g = workloads.transformer_lm(n_layers=1, d_model=128, n_heads=4,
                                 seq=16, vocab=64)
    chip = default_chip(n_cores=2, mesh_cols=1, n_macro_groups=2,
                        macros_per_group=2)
    model, rep, oracle, cg = _run_both(g, chip, batch=2)
    _check_final(model, rep, oracle, cg)
    dyn_rounds = max(sc.n_rounds for st in model.stages
                     for sc in st.schedules
                     if sc.weight_source == "dynamic")
    assert dyn_rounds > 1, "expected multi-round dynamic streaming"


def test_transformer_func_matches_jax_reference():
    """Acceptance: func-mode output == the JAX reference.

    The reference is an *independent* jnp forward pass — per-head
    einsum attention instead of block-diagonal matrices, the shared
    integer softmax/layernorm/gelu semantics re-implemented in jnp —
    checked against the functional ISS output of the compiled model.
    """
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.core import vecsem

    H, dh, seq, d, vocab = 2, 32, 8, 64, 32
    g = workloads.transformer_lm(n_layers=1, d_model=d, n_heads=H,
                                 seq=seq, vocab=vocab)
    cg = g.condense()
    chip = default_chip(n_cores=8, mesh_cols=4)
    res = partition(cg, chip, "dp", CostParams(batch=2))
    weights, biases = _weights_for(cg)
    inputs = RNG.integers(-8, 8, (2, seq, d)).astype(np.int8)
    qp = ref.auto_quant(cg, weights, biases, inputs)
    model = compile_model(res, batch=2, quant=qp, strict_lmem=True)
    img = model.build_gmem_image(weights, biases, inputs)
    rep = Simulator(chip, model.isa, mode="func").run_model(
        model, gmem_image=img)

    gid = {grp.name: grp.idx for grp in cg}
    with jax.enable_x64(True):
        EXP2 = jnp.asarray(vecsem.EXP2_LUT)
        GELU = jnp.asarray(vecsem.GELU_LUT)

        def j_quant(acc, gd):
            q = qp[gd]
            den = 1 << q.shift
            v = (acc.astype(jnp.int64) * q.scale + (den >> 1)) // den
            return jnp.clip(v, -128, 127).astype(jnp.int8)

        def j_lin(x, gd):
            w = jnp.asarray(weights[gd], jnp.int32)
            return j_quant(x.astype(jnp.int32) @ w, gd)

        def j_softmax(x):
            xi = x.astype(jnp.int64)
            dd = jnp.clip(xi.max(-1, keepdims=True) - xi, 0, 255)
            e = EXP2[dd]
            s = e.sum(-1, keepdims=True)
            return jnp.clip((127 * e + (s >> 1)) // s, 0,
                            127).astype(jnp.int8)

        def j_layernorm(x):
            xi = x.astype(jnp.int64)
            n = x.shape[-1]
            s = xi.sum(-1, keepdims=True)
            dv = n * xi - s
            ss = (dv * dv).sum(-1, keepdims=True)
            r = jnp.sqrt((ss // n).astype(jnp.float64)).astype(jnp.int64)
            r = jnp.where(r * r > ss // n, r - 1, r)
            r = jnp.where((r + 1) * (r + 1) <= ss // n, r + 1, r) + 1
            y = (2 * vecsem.LN_GAIN * dv + r) // (2 * r)
            return jnp.clip(y, -128, 127).astype(jnp.int8)

        def j_sat_add(a, b):
            return jnp.clip(a.astype(jnp.int16) + b.astype(jnp.int16),
                            -128, 127).astype(jnp.int8)

        def heads(x):                     # (seq, d) -> (H, seq, dh)
            return x.reshape(seq, H, dh).transpose(1, 0, 2)

        outs = []
        for s in range(2):
            x = jnp.asarray(inputs[s])
            e_ = j_lin(x, gid["embed"])
            qv = heads(j_lin(e_, gid["l0.attn.q"])).astype(jnp.int32)
            kv = heads(j_lin(e_, gid["l0.attn.k"])).astype(jnp.int32)
            vv = heads(j_lin(e_, gid["l0.attn.v"])).astype(jnp.int32)
            sc = j_quant(jnp.einsum("hmd,hnd->hmn", qv, kv),
                         gid["l0.attn.scores"])
            sm = j_softmax(sc).astype(jnp.int32)
            ctx = j_quant(jnp.einsum("hmn,hnd->hmd", sm, vv),
                          gid["l0.attn.ctx"])
            ctx = ctx.transpose(1, 0, 2).reshape(seq, d)
            o = j_lin(ctx, gid["l0.attn.o"])
            x1 = j_layernorm(j_sat_add(o, e_))
            up = GELU[j_lin(x1, gid["l0.up"]).astype(jnp.int16) + 128]
            dn = j_lin(up, gid["l0.down"])
            x2 = j_layernorm(j_sat_add(dn, x1))
            outs.append(np.asarray(j_lin(x2, gid["lm_head"])))

    last = len(cg) - 1
    for s in range(2):
        addr, nb = model.output_addr(last, s)
        got = rep.gmem[addr - 0x10000000: addr - 0x10000000 + nb]
        np.testing.assert_array_equal(
            got, outs[s].reshape(-1),
            err_msg=f"func-mode output != JAX reference (sample {s})")


def test_perf_mode_matches_func_timing():
    """perf mode (no data) must report identical cycle counts."""
    g = workloads.tiny_cnn(res=8, c=8)
    cg = g.condense()
    chip = default_chip(n_cores=8, mesh_cols=4)
    res = partition(cg, chip, "dp", CostParams(batch=2))
    weights, biases = _weights_for(cg)
    inputs = RNG.integers(-8, 8, (2, 8, 8, 3)).astype(np.int8)
    qp = ref.auto_quant(cg, weights, biases, inputs)
    model = compile_model(res, batch=2, quant=qp, strict_lmem=True)
    img = model.build_gmem_image(weights, biases, inputs)
    f = Simulator(chip, model.isa, mode="func").run_model(model, img)
    p = Simulator(chip, model.isa, mode="perf").run_model(model)
    assert f.cycles == p.cycles
    assert f.events["cim_macro_passes"] == p.events["cim_macro_passes"]
