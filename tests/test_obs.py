"""Program spans (:mod:`repro.obs`): a no-op without JAX, profiler
events with their counters under ``jax.profiler``."""

import glob
import os
import subprocess
import sys
import textwrap

import pytest

from repro import flow
from repro.core.arch import default_chip
from repro.core.mapping import CostParams
from repro.obs import span

# jaxlib warns once as it builds the type of an event's stats
pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats has no __module__")

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


def test_span_without_jax_is_a_shared_noop_and_imports_nothing():
    code = textwrap.dedent("""
        import sys
        from repro.core.arch import default_chip
        from repro.core.mapping import CostParams
        from repro.core.simulator import Simulator
        from repro import flow
        from repro.obs import span

        a, b = span("repro.x", rows=3), span("repro.y")
        assert a is b
        with a as s:
            s.set_metadata(bytes_down=1)
        # the host-only path, every span of it passed, loads no JAX
        art = flow.compile("tiny_cnn", default_chip(), flow.CompileOptions(
            params=CostParams(batch=2), fidelity="simulate"))
        Simulator(default_chip(), art.model.isa,
                  engine="vector").run_model(art.model)
        print(sorted(m for m in sys.modules
                     if m == "jax" or m.startswith(("jax.", "jaxlib"))))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


def _trace(tmp_path, body):
    """Run ``body`` under a ``jax.profiler`` session (python tracer off)
    and return the host events named ``repro.*`` as ``(line, name,
    start_ns, end_ns, stats)``, in start order."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        body()
    pb = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                   recursive=True)
    pd = ProfileData.from_file(max(pb, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((line.name, e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda x: (x[2], x[2] - x[3]))


def test_nested_spans_land_on_one_line_with_their_counters(tmp_path):
    def body():
        with span("repro.outer", machines=3) as sp:
            with span("repro.inner", rows=7, bucket=8):
                sum(range(1000))
            sp.set_metadata(bytes_down=64)
    (l1, n1, s1, e1, st1), (l2, n2, s2, e2, st2) = _trace(tmp_path, body)
    assert (n1, n2) == ("repro.outer", "repro.inner")
    assert l1 == l2
    assert s1 <= s2 < e2 <= e1
    assert st1 == {"machines": 3, "bytes_down": 64}
    assert st2 == {"rows": 7, "bucket": 8}


def test_a_span_closes_when_its_body_raises(tmp_path):
    def body():
        with pytest.raises(ValueError):
            with span("repro.fails"):
                raise ValueError("boom")
        with span("repro.after"):
            pass
    (_, n1, s1, e1, _), (_, n2, s2, _, _) = _trace(tmp_path, body)
    assert (n1, n2) == ("repro.fails", "repro.after")
    assert e1 <= s2


def test_pass_record_times_the_flow_span(tmp_path):
    """``PassRecord.wall_s`` and the ``repro.flow.<pass>`` span read the
    same interval, up to the clock reads at its two ends."""
    arts = []

    def body():
        pipe = flow.Pipeline()            # a cold pass cache
        arts.append(pipe.compile("tiny_cnn", default_chip(),
                                 flow.CompileOptions(
                                     params=CostParams(batch=2),
                                     fidelity="simulate")))
    spans = _trace(tmp_path, body)
    recs = arts[0].trace
    assert [r.name for r in recs] == ["condense", "partition:dp", "codegen"]
    assert [n for _, n, *_ in spans] == [f"repro.flow.{r.name}"
                                         for r in recs]
    model = arts[0].model
    for rec, (_, _, s, e, stats) in zip(recs, spans):
        want = {"cached": int(rec.cached)}
        if rec.name == "codegen":
            # codegen's span also counts the weights it places and loads
            want.update(weight_bytes=model.weight_bytes,
                        weight_load_bytes=model.weight_load_bytes)
        assert stats == want
        assert (e - s) / 1e9 == pytest.approx(rec.wall_s, abs=5e-3)


def test_oracle_span_counts_its_contractions_by_path(tmp_path):
    """``func:pallas`` with ``check=True`` sets the ``repro.ref.oracle``
    span's counter: every contraction of the numpy oracle, all on the
    exact float64 path."""
    art = flow.compile("tiny_cnn", default_chip(), flow.CompileOptions(
        strategy="dp", batch=2, workload_kw={"res": 8},
        fidelity="analytic"))
    spans = _trace(tmp_path, lambda: art.evaluate("func:pallas"))
    oracle = [stats for _, n, _, _, stats in spans
              if n == "repro.ref.oracle"]
    n_mvm = sum(g.anchor is not None for g in art.cg)
    assert oracle == [{"f64": 2 * n_mvm}]
