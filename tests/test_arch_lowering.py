"""The normal path for model configurations: an ``ArchConfig`` lowered
to a graph (``workloads.build("arch", ...)``) runs every rung, and a
tiny Ouro-shaped looped LM is value-exact in func mode.

Ouro's block (sandwich RMSNorm, RoPE, SwiGLU, loop-shared weights, a
per-step exit gate) at d 64, 4 heads of 16, d_ff 176, seq 8, 2 layers x
2 loop steps; the functional ISS, ``func:pallas`` and the numpy oracle
agree bit for bit on every group output.  The integer semantics of the
new vector ops are checked against direct float-rounded formulas.
"""

import glob
import math
import os

import numpy as np
import pytest

from repro import flow
from repro.core import ref, vecsem, workloads
from repro.core.arch import default_chip
from repro.core.codegen import _compile_model
from repro.core.mapping import gmem_footprint_bytes
from repro.core.simulator import Simulator
from repro.flow import CompileOptions

TINY = dict(arch="ouro-2.6b", seq=8, layers=2, loop_steps=2, d_model=64,
            n_heads=4, n_kv_heads=4, head_dim=16, d_ff=176)
# a 16-core chip with 2 single-macro groups per core: the tiny model
# spreads over 16 stages with multi-core replicas, so taps and shared
# weights cross stage boundaries
SMALL = dict(n_cores=16, mesh_cols=4, n_macro_groups=2, macros_per_group=1)

pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats has no __module__")


@pytest.fixture(scope="module")
def tiny():
    cg = workloads.build("arch", **TINY).condense()
    w, b, x = ref.random_init(cg, batch=2, seed=1)
    norms = ref.random_norms(cg, seed=2)
    q = ref.auto_quant(cg, w, b, x, norms=norms)
    want = ref.run_reference(cg, w, b, q, x, norms=norms)
    return cg, w, b, x, norms, q, want


# ---------------------------------------------------------------------------
# integer semantics against direct float-rounded formulas
# ---------------------------------------------------------------------------


def _round_half_up(v):
    return np.floor(np.asarray(v, dtype=np.float64) + 0.5)


def test_rmsnorm_i8_is_the_float_formula_rounded():
    rng = np.random.default_rng(0)
    x = rng.integers(-128, 128, (64, 48)).astype(np.int8)
    n = x.shape[-1]
    xf = x.astype(np.float64)
    # r = isqrt(n * sum(x^2)) + 1 is the n-scaled rms, so
    # y = round(48 x * n / r)
    r = np.floor(np.sqrt(n * (xf * xf).sum(-1, keepdims=True))) + 1
    want = np.clip(_round_half_up(48 * n * xf / r), -128, 127)
    np.testing.assert_array_equal(vecsem.rmsnorm_i8(x), want)
    # and it is layernorm's unit with the centring off
    np.testing.assert_array_equal(vecsem.rmsnorm_i8(x),
                                  vecsem.layernorm_i8(x, center=False))


@pytest.mark.parametrize("fn,scale,lo", [("silu", None, -128),
                                         ("sigmoid", 127.0, 0)])
def test_lut_activations_are_the_float_formula_rounded(fn, scale, lo):
    v = np.arange(-128, 128).astype(np.int8)
    sig = 1.0 / (1.0 + np.exp(-v.astype(np.float64) / 16.0))
    want = np.round(v * sig) if scale is None else np.round(scale * sig)
    got = getattr(vecsem, f"{fn}_i8")(v)
    np.testing.assert_array_equal(got, np.clip(want, lo, 127))
    assert got.dtype == np.int8


def test_glu_is_the_rounded_product():
    rng = np.random.default_rng(1)
    gate = rng.integers(-128, 128, 4096).astype(np.int8)
    up = rng.integers(-128, 128, 4096).astype(np.int8)
    for shift in (0, 3, 7):
        p = vecsem.silu_i8(gate).astype(np.float64) * up
        want = np.clip(_round_half_up(p / 2 ** shift), -128, 127)
        np.testing.assert_array_equal(vecsem.glu_i8(gate, up, shift), want)


def test_rope_is_the_float_rotation_rounded():
    rng = np.random.default_rng(2)
    hd, rows, theta = 16, 12, 1e6
    x = rng.integers(-128, 128, (rows, hd)).astype(np.int8)
    tab = vecsem.rope_table(rows, hd, theta)
    half = hd // 2
    ang = (np.arange(rows)[:, None]
           * theta ** (-np.arange(half) * 2.0 / hd)[None, :])
    np.testing.assert_array_equal(tab[:, :half],
                                  np.round(2 ** 14 * np.cos(ang)))
    np.testing.assert_array_equal(tab[:, half:],
                                  np.round(2 ** 14 * np.sin(ang)))
    a, b = x[:, :half].astype(np.float64), x[:, half:].astype(np.float64)
    c, s = tab[:, :half].astype(np.float64), tab[:, half:].astype(np.float64)
    want = np.concatenate([a * c - b * s, b * c + a * s], axis=1) / 2 ** 14
    got = vecsem.rope_i8(x, tab)
    np.testing.assert_array_equal(got, np.clip(_round_half_up(want),
                                               -128, 127))
    # row 0 is position 0: no rotation
    np.testing.assert_array_equal(got[0], x[0])
    # and every element stays within one unit of the exact rotation
    exact = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                            b * np.cos(ang) + a * np.sin(ang)], axis=1)
    inside = np.abs(exact) < 127
    assert np.abs(got[inside] - exact[inside]).max() <= 1.0


def test_causal_softmax_is_the_float_formula_over_each_prefix():
    rng = np.random.default_rng(3)
    rows, heads, seg = 6, 3, 8
    x = rng.integers(-128, 128, (rows, heads * seg)).astype(np.int8)
    got = vecsem.causal_softmax_i8(x, seg).reshape(rows, heads, seg)
    xs = x.reshape(rows, heads, seg).astype(np.float64)
    for r in range(rows):
        d = np.clip(xs[r, :, :r + 1].max(-1, keepdims=True)
                    - xs[r, :, :r + 1], 0, 255)
        e = np.round(2 ** 14 * 2 ** (-d / 16))
        want = np.floor(127 * e / e.sum(-1, keepdims=True) + 0.5)
        np.testing.assert_array_equal(got[r, :, :r + 1], want)
        assert not got[r, :, r + 1:].any()


def test_attention_is_causal(tiny):
    """Token ``r`` attends to tokens ``0..r``: the system's attention
    probabilities read 0 on every later key, and the same graph without
    the mask gives other probabilities and another final state."""
    cg, w, b, x, norms, q, want = tiny
    seq, heads = TINY["seq"], TINY["n_heads"]
    scores = [g for g in cg if g.name.endswith(".scores")]
    assert len(scores) == TINY["layers"] * TINY["loop_steps"]
    later = np.triu(np.ones((seq, seq), dtype=bool), 1)
    for g in scores:
        p = want[g.idx].reshape(-1, seq, heads, seq).transpose(0, 2, 1, 3)
        assert not p[:, :, later].any(), g.name
        assert p[:, :, ~later].any(), g.name
    open_ = workloads.build("arch", **TINY).condense()
    for op in open_.source.ops:
        op.attrs.pop("causal", None)
    got = ref.run_reference(open_, w, b, q, x, norms=norms)
    first = scores[0].idx
    assert got[first][:, np.tile(later, (1, heads)).reshape(-1)].any()
    assert not np.array_equal(got[first], want[first])
    last = [g for g in cg if g.name.endswith(".down")][-1]
    assert not np.array_equal(got[last.idx], want[last.idx])


# ---------------------------------------------------------------------------
# the lowering
# ---------------------------------------------------------------------------


def test_published_widths_give_51m_weights_per_layer():
    g = workloads.build("arch", arch="ouro-2.6b", seq=256, layers=4)
    cg = g.condense()
    per_layer = {}
    for op in g.ops:
        key = op.attrs.get("param", "")
        if op.is_mvm and not op.attrs.get("dynamic_weights") \
                and "owner" not in op.attrs and key.startswith("l"):
            layer = key.split(".")[0]
            per_layer[layer] = per_layer.get(layer, 0) + op.weight_elems
    assert per_layer == {f"l{i}": 51_380_224 for i in range(44, 48)}
    # 4 loop steps of 4 layers (9 MVM groups each) and an exit gate per
    # step; the weights are held once
    assert len(cg) == 4 * (4 * 9 + 1)
    assert cg.unique_weight_bytes == 4 * 51_380_224 + 2048
    stored = sum(g.weight_bytes for g in cg if not g.dynamic_weights)
    assert stored == 4 * cg.unique_weight_bytes


def test_legacy_workloads_keep_their_groups():
    """No legacy graph gains a shared owner or an input-side op, and
    the only tap is efficientnet's (a conv group, legacy tail)."""
    for name in sorted(workloads.WORKLOADS):
        if name == "arch":
            continue
        cg = workloads.build(name).condense()
        assert all(g.owner is None for g in cg)
        assert not any(op.attrs.get("pre") for op in cg.source.ops)
        taps = [g for g in cg if g.tap is not None]
        assert not taps or name == "efficientnetb0"


def test_shared_weights_are_one_gmem_copy(tiny):
    cg, w, b, x, norms, q, _ = tiny
    unrolled = sum(g.weight_bytes for g in cg if not g.dynamic_weights)
    assert gmem_footprint_bytes(cg.groups) * TINY["loop_steps"] == unrolled
    art = flow.compile("arch", default_chip(**SMALL), CompileOptions(
        strategy="dp", batch=2, quant=q, fidelity="simulate",
        workload_kw=TINY))
    cm = art.model
    assert cm.weight_bytes == unrolled // TINY["loop_steps"]
    # every loop step re-streams its weights: the batch's loads cover
    # the unrolled stack at least once
    assert cm.weight_load_bytes * cm.batch >= unrolled
    owners = {key[0] for key in cm.layout.weights}
    assert owners == {g.idx for g in cg if g.owner is None
                      and not g.dynamic_weights}


def _func_outputs(cg, chip, w, b, x, norms, q):
    """Every group's outputs from the functional ISS, all groups made
    boundary outputs so that each lands in gmem."""
    art = flow.compile("arch", chip, CompileOptions(
        strategy="dp", batch=x.shape[0], quant=q, strict_lmem=True,
        workload_kw=TINY))
    cm = _compile_model(art.partition, batch=x.shape[0], quant=q,
                        strict_lmem=True,
                        force_boundary=set(range(len(cg))))
    img = cm.build_gmem_image(w, b, x, norms=norms)
    rep = Simulator(chip, cm.isa, mode="func").run_model(cm, img)
    out = {}
    for (gid, s), (addr, nb) in cm.layout.acts.items():
        out.setdefault(gid, {})[s] = rep.gmem[addr - 0x10000000:
                                              addr - 0x10000000 + nb]
    return cm, out


@pytest.mark.parametrize("chip_kw", [SMALL, {}], ids=["16-core", "tab1"])
def test_func_iss_is_bit_exact_on_every_group(tiny, chip_kw):
    cg, w, b, x, norms, q, want = tiny
    cm, got = _func_outputs(cg, default_chip(**chip_kw), w, b, x, norms,
                            q)
    assert set(got) == {g.idx for g in cg}
    for g in cg:
        for s in range(x.shape[0]):
            np.testing.assert_array_equal(
                got[g.idx][s], want[g.idx][s].reshape(-1),
                err_msg=f"{g.name} sample {s}")
    if chip_kw:
        assert len(cm.stages) > 4


def test_pre_norm_blocks_are_bit_exact():
    """``norm_placement="pre"``: no N2/N4, the stream's taps feed the
    residual adds directly."""
    kw = dict(TINY, norm_placement="pre")
    cg = workloads.build("arch", **kw).condense()
    assert not any(cg.source.ops[i].name.endswith((".n2", ".n4"))
                   for g in cg for i in g.op_ids)
    w, b, x = ref.random_init(cg, batch=1, seed=3)
    norms = ref.random_norms(cg, seed=4)
    q = ref.auto_quant(cg, w, b, x, norms=norms)
    want = ref.run_reference(cg, w, b, q, x, norms=norms)
    chip = default_chip(**SMALL)
    art = flow.compile("arch", chip, CompileOptions(
        strategy="dp", batch=1, quant=q, strict_lmem=True,
        fidelity="simulate", workload_kw=kw))
    cm = art.model
    rep = Simulator(chip, cm.isa, mode="func").run_model(
        cm, cm.build_gmem_image(w, b, x, norms=norms))
    assert cm.layout.acts
    for (gid, s), (addr, nb) in cm.layout.acts.items():
        np.testing.assert_array_equal(
            rep.gmem[addr - 0x10000000:addr - 0x10000000 + nb],
            want[gid][s].reshape(-1), err_msg=cg[gid].name)


def test_func_pallas_and_the_oracle_agree_with_a_live_signal(tiny):
    cg, w, b, x, norms, q, want = tiny
    art = flow.compile("arch", default_chip(**SMALL), CompileOptions(
        strategy="dp", batch=2, workload_kw=TINY))
    rep = art.evaluate("func:pallas", weights=w, biases=b, inputs=x,
                       quant=q, norms=norms, check=True)
    for g in cg:
        np.testing.assert_array_equal(rep.outputs[g.idx], want[g.idx])
    # the last step's state (its final norm, the last down group's
    # output) is at least half non-zero: agreement is not vacuous
    last = [g for g in cg if g.name.endswith(".down")][-1]
    state = want[last.idx]
    assert np.count_nonzero(state) / state.size >= 0.5
    exits = [g for g in cg if g.name.endswith(".exit")]
    assert len(exits) == TINY["loop_steps"]
    assert all(np.count_nonzero(want[g.idx]) for g in exits)


@pytest.mark.parametrize("fidelity", ["analytic", "trace", "simulate"])
def test_every_rung_runs(fidelity):
    art = flow.compile("arch", default_chip(**SMALL), CompileOptions(
        strategy="dp", batch=2, fidelity=fidelity, workload_kw=TINY))
    rep = art.evaluate(fidelity)
    assert rep.cycles > 0 and math.isfinite(rep.cycles)


def test_perf_engines_agree_with_func_timing(tiny):
    cg, w, b, x, norms, q, _ = tiny
    chip = default_chip(**SMALL)
    art = flow.compile("arch", chip, CompileOptions(
        strategy="dp", batch=2, quant=q, strict_lmem=True,
        fidelity="simulate", workload_kw=TINY))
    cm = art.model
    func = Simulator(chip, cm.isa, mode="func").run_model(
        cm, cm.build_gmem_image(w, b, x, norms=norms))
    for engine in ("scalar", "vector", "jax"):
        perf = Simulator(chip, cm.isa, engine=engine).run_model(cm)
        assert perf.cycles == func.cycles, engine
        assert perf.events == func.events, engine


def test_gqa_and_moe_are_refused():
    with pytest.raises(ValueError, match="grouped-query"):
        workloads.build("arch", arch="phi4-mini-3.8b", seq=8, layers=1)
    with pytest.raises(ValueError, match="dense"):
        workloads.build("arch", arch="olmoe-1b-7b", seq=8, layers=1)


# ---------------------------------------------------------------------------
# spans and counters
# ---------------------------------------------------------------------------


def _repro_events(tmp_path, body):
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        body()
    pb = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                   recursive=True)
    pd = ProfileData.from_file(max(pb, key=os.path.getmtime))
    return [(e.name, dict(e.stats)) for plane in pd.planes
            if plane.name == "/host:CPU" for line in plane.lines
            for e in line.events if e.name.startswith("repro.")]


def test_lowering_codegen_and_device_call_counters(tmp_path):
    from repro.core.jaxsim import FleetStageDecoder
    from repro.core.machine import machine_for
    from repro.flow.pipeline import Pipeline

    chip = default_chip(**SMALL)
    seen = {}

    def body():
        art = Pipeline().compile("arch", chip, CompileOptions(
            strategy="dp", batch=1, fidelity="simulate",
            workload_kw=TINY))
        seen["cm"] = cm = art.model
        dec = FleetStageDecoder(cm.isa, [machine_for(chip)] * 2)
        for sp in cm.stages + cm.stages[:1]:   # the first one again
            dec.decode_stage(sp.programs)

    events = _repro_events(tmp_path, body)
    cm = seen["cm"]
    lower = [s for n, s in events if n == "repro.flow.lower"]
    assert lower == [{"layers": 2, "loop_steps": 2, "seq": 8}]
    (cg_stats,) = [s for n, s in events if n == "repro.flow.codegen"]
    assert cg_stats["weight_bytes"] == cm.weight_bytes > 0
    assert cg_stats["weight_load_bytes"] == cm.weight_load_bytes
    names = [n for n, _ in events
             if n.startswith("repro.sim.device_call")]
    # the stage decoded again is marked, just before its call
    assert names == (["repro.sim.device_call"] * len(cm.stages)
                     + ["repro.sim.device_call.repeat",
                        "repro.sim.device_call"])
