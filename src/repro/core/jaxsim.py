"""JAX backend for the pre-decoded perf engine (``engine="jax"``).

:mod:`repro.core.vectorsim` already reduced perf-mode decode to a fixed
set of array passes over a stage's concatenated instruction columns —
segmented cumulative sums for G_Reg/S_Reg dataflow, a cumulative OR for
macro-group occupancy, and batched :class:`~repro.core.machine.
MachineModel` latency lookups.  This module re-expresses exactly those
passes in ``jax.numpy`` as **one jitted XLA program per decode-table
shape** and, crucially, makes the machine's timing constants a
*function argument* instead of baked-in Python attributes:

* ``Simulator(engine="jax")`` — single machine.  The device pass runs
  with donated input buffers and returns per-instruction latencies plus
  the resolved register/sreg/occupancy values; the host then assembles
  replay items with the *identical numpy expressions* the numpy engine
  uses (:func:`vectorsim._finish_decode`), so every reported number —
  cycles, stage_cycles, unit_busy, events, instrs — is bit-identical.
* :class:`FleetStageDecoder` — many machines.  The timing constants
  stack into a :class:`MachineTables` pytree and the same device pass is
  ``vmap``-ed over the machine axis: *one* XLA program evaluates a whole
  chunk of DSE points ("same program, different chip constants").  The
  dataflow half of the pass depends only on the instruction columns, so
  under ``vmap(in_axes=(0, None))`` XLA computes it once and batches
  only the latency arithmetic.

Bit-identity strategy: the device computes and returns only
*per-instruction* int64 values — resolved operands and the integer
vector / MVM latencies, mirrored term-for-term from
:class:`MachineModel`'s ``*_cycles_array`` methods.  No float64 touches
the device: the TPU emulates it with less than IEEE precision (its
divisions, int64 conversions and even an upload/download round trip of
a float64 constant differ from numpy), so the float latencies
(constants, ``rows / rate``, ``bytes / link width``) and every *sum*
(event ledgers, unit-busy, run prefix sums) happen on the host in the
shared numpy back half.  Inputs are padded to power-of-two buckets to
bound jit recompiles; all scans are prefix-safe, so padding appended
after the real rows never perturbs them and outputs are sliced back to
the true length.

Int semantics: everything runs under ``jax.enable_x64(True)`` so
register arithmetic wraps in int64 exactly like the numpy engine.
Programs with control flow / scalar-ALU chains take the same
decode-time unroll path as the numpy engine; anything undecodable falls
back to the scalar interpreter per stage, unchanged.
"""

from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..obs import recording, span
from .isa import Isa, Program
from .machine import MachineModel
from .vectorsim import (
    StageDecoder, DecodeUnsupported, _DecodedStage, _Prep, _finish_decode,
    replay_stage, _END, _K_VEC, _K_MVM, _K_WLOAD, _K_BCAST,
    _K_SEND, _K_RECV, _K_GLD, _K_GST, _K_SYNC, _K_HALT,
    _S_VLEN, _S_VREP, _S_CHANNEL, _S_MASK_LO, _S_MASK_HI,
    _S_SEG_IN, _S_SEG_OUT, _S_NLEN, _I8_FLAG,
)

__all__ = ["JaxStageDecoder", "FleetStageDecoder", "MachineTables",
           "run_stage"]

# machine timing-constant layout (order is the device-call ABI)
_INT_KEYS = ("vector_lanes", "vector_alu_latency", "vector_mul_latency",
             "vector_special_latency", "mvm_interval_beats",
             "mvm_fill_beats")

# instruction columns shipped to the device (plus op / starts)
_COL_NAMES = ("dst", "a", "imm", "sreg", "src", "len", "rows", "mg",
              "rep", "core", "size")

# tracked S_Reg timeline columns, in device order
_SREG_IDS = np.array([_S_VLEN, _S_VREP, _S_CHANNEL, _S_MASK_LO,
                      _S_MASK_HI, _S_SEG_IN, _S_SEG_OUT, _S_NLEN],
                     dtype=np.int64)
_SREG_KEYS = ("vlen", "vrep", "chan", "mask_lo", "mask_hi",
              "seg_in", "seg_out", "nlen")
_VLEN_COL = 0


class MachineTables:
    """Stacked timing constants — the ``vmap`` axis of a fleet.

    ``arrays`` is a tuple of ``(n_machines,)`` int64 columns in
    ``_INT_KEYS`` order, built from
    :meth:`MachineModel.timing_constants` so the batched latency
    arithmetic stays bit-identical to each machine's own accessors.
    """

    __slots__ = ("arrays", "n_machines")

    def __init__(self, arrays: Tuple[np.ndarray, ...]) -> None:
        self.arrays = arrays
        self.n_machines = int(arrays[0].shape[0])

    @classmethod
    def stack(cls, machines: List[MachineModel]) -> "MachineTables":
        with span("repro.sim.decode_prep", machines=len(machines)):
            rows = [m.timing_constants() for m in machines]
            return cls(tuple(np.array([r[k] for r in rows],
                                      dtype=np.int64)
                             for k in _INT_KEYS))


def _scalar_row(tc: Dict[str, int]) -> Tuple[np.ndarray, ...]:
    """One machine's constants as 0-d arrays (the unbatched call)."""
    return tuple(np.int64(tc[k]) for k in _INT_KEYS)


def _bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b <<= 1
    return b


# ---------------------------------------------------------------------------
# Device pass
# ---------------------------------------------------------------------------

_EXEC_CACHE: Dict[tuple, Tuple[Any, Any]] = {}
# the register bucket each compiled stage pass was built for, by
# function (a device call's ``regs`` counter)
_EXEC_REGS: Dict[Any, int] = {}


def _build_exec(kind_t: np.ndarray, vcls_t: np.ndarray,
                ids: Tuple[int, ...], n_regs: int) -> Tuple[Any, Any]:
    """Compile the stage pass for one ISA-table fingerprint.

    Returns ``(single, fleet)`` where ``single(sc, cols)`` evaluates one
    machine (donated buffers) and ``fleet`` is the same function vmapped
    over the machine axis of ``sc``.  ``n_regs`` bounds the dense G_Reg
    timeline width (a power of two ≤ 32, from the stage's columns).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..compile_cache import enable_compile_cache
    enable_compile_cache()
    id_lui, id_addi, id_cfg, id_cfgr, id_setvl = ids
    kind_c = jnp.asarray(kind_t.astype(np.int32))
    vcls_c = jnp.asarray(vcls_t.astype(np.int32))
    sreg_ids = jnp.asarray(_SREG_IDS)

    def stage_pass(sc, cols):
        lanes, v_alu, v_mul, v_special, ivl, fill = sc
        op = cols["op"]
        starts = cols["starts"]
        n = op.shape[0]
        idx = jnp.arange(n, dtype=jnp.int64)
        kind = kind_c[op]
        vcls = vcls_c[op]

        def excl_cummax(x):
            inc = lax.cummax(x, axis=0)
            pad = jnp.full_like(x[:1], -1)
            return jnp.concatenate([pad, inc[:-1]], axis=0)

        # ---- G_Reg dataflow: dense (n, n_regs) chain-cumsum ----------
        # column r tracks register r; reads gather the value written by
        # the last write strictly before the reader, within the reader's
        # program (``lastw >= starts`` — never another core's writes)
        is_lui = op == id_lui
        is_addi = op == id_addi
        dst, a_col, imm = cols["dst"], cols["a"], cols["imm"]
        wr = (is_lui | is_addi) & (dst != 0)
        regs = jnp.arange(n_regs, dtype=jnp.int64)
        w = wr[:, None] & (dst[:, None] == regs[None, :])
        base = jnp.where(is_lui, (imm & 0xFFFF) << 16, imm)
        lastw = excl_cummax(jnp.where(w, idx[:, None], -1))
        firstw = lastw < starts[:, None]
        reset = w & (is_lui[:, None] | (a_col[:, None] != regs[None, :])
                     | firstw)
        contrib = jnp.where(w, jnp.where(reset, base[:, None],
                                         imm[:, None]), 0)
        c = jnp.cumsum(contrib, axis=0)
        lastreset = lax.cummax(jnp.where(reset, idx[:, None], -1), axis=0)
        before = c - contrib                     # cumsum exclusive of row
        vals = c - jnp.take_along_axis(before, jnp.maximum(lastreset, 0),
                                       axis=0)
        vis = jnp.where(lastw >= starts[:, None],
                        jnp.take_along_axis(vals, jnp.maximum(lastw, 0),
                                            axis=0), 0)

        def greg_read(col):
            return jnp.take_along_axis(vis, col[:, None], axis=1)[:, 0]

        rd_src = greg_read(cols["src"])
        rd_core = greg_read(cols["core"])
        rd_size = greg_read(cols["size"])

        # ---- S_Reg timelines: dense (n, 8) last-write gather ---------
        is_cfg = op == id_cfg
        is_cfgr = op == id_cfgr
        is_setvl = op == id_setvl
        sreg = cols["sreg"]
        sw = (is_cfg | is_cfgr)[:, None] & (sreg[:, None]
                                            == sreg_ids[None, :])
        sw = sw.at[:, _VLEN_COL].set(sw[:, _VLEN_COL] | is_setvl)
        sval = jnp.where(is_cfgr, rd_src,
                         jnp.where(is_setvl, cols["len"], imm))
        slast = excl_cummax(jnp.where(sw, idx[:, None], -1))
        scur = jnp.where(
            slast >= starts[:, None],
            jnp.take_along_axis(jnp.broadcast_to(sval[:, None], sw.shape),
                                jnp.maximum(slast, 0), axis=0), 0)

        # ---- MG occupancy: dense (n, 32) last-load timeline ----------
        # bit g of ``loaded`` is set when the reader's program loaded MG
        # g strictly before it (MG ids are 5-bit register fields) — the
        # segmented cumulative OR of the numpy engine, as one cummax: a
        # ``lax.associative_scan`` here took minutes of TPU compile at
        # 10^5-row buckets, a cummax seconds
        is_wl = kind == _K_WLOAD
        mgs = jnp.arange(32, dtype=jnp.int64)
        mw = is_wl[:, None] & (cols["mg"][:, None] == mgs[None, :])
        mlast = excl_cummax(jnp.where(mw, idx[:, None], -1))
        loaded = jnp.sum(jnp.where(mlast >= starts[:, None],
                                   jnp.asarray(1, jnp.int64) << mgs, 0),
                         axis=1)

        # ---- integer latencies (term-for-term MachineModel mirrors) --
        # vector and MVM latencies are exact int64 here; the float ones
        # (constants, rows / rate, bytes / link width) are priced on the
        # host, where division rounds as IEEE float64 on every backend
        n_el = (jnp.maximum(scur[:, 0], 1)       # vlen
                * jnp.maximum(scur[:, 1], 1))    # vrep
        n_el = jnp.maximum(n_el, 1)
        beats = -(-n_el // lanes)                # ceil-div, exact int64
        vlat = jnp.where(vcls == 2, beats * v_special,
                         beats + jnp.where(vcls == 1, v_mul, v_alu))
        lat = jnp.where(kind == _K_VEC, vlat,
                        jnp.where(kind == _K_MVM,
                                  cols["rep"] * ivl + fill, 0))

        resolved = {"core": rd_core, "size": rd_size, "loaded": loaded}
        for k, key in enumerate(_SREG_KEYS):
            resolved[key] = scur[:, k]
        return lat, resolved

    single = jax.jit(stage_pass, donate_argnums=(1,))
    fleet = jax.jit(jax.vmap(stage_pass, in_axes=(0, None),
                             out_axes=(0, None)))
    _EXEC_REGS[single] = _EXEC_REGS[fleet] = n_regs
    return single, fleet


def _exec_for(dec: StageDecoder, n_regs: int) -> Tuple[Any, Any]:
    key = (dec.kind.tobytes(), dec.vcls.tobytes(),
           dec.id_lui, dec.id_addi, dec.id_cfg, dec.id_cfgr,
           dec.id_setvl, n_regs)
    got = _EXEC_CACHE.get(key)
    if got is None:
        got = _EXEC_CACHE[key] = _build_exec(
            dec.kind, dec.vcls,
            (dec.id_lui, dec.id_addi, dec.id_cfg, dec.id_cfgr,
             dec.id_setvl), n_regs)
    return got


# ---------------------------------------------------------------------------
# Host halves
# ---------------------------------------------------------------------------


def _reg_bucket(pr: _Prep) -> int:
    """Dense G_Reg width for this stage (power of two, ≤ 32).

    Raises :class:`DecodeUnsupported` when a register operand falls
    outside the architectural file — the caller then takes the scalar
    fallback, exactly like any other undecodable stage.
    """
    hi = 0
    for name in ("dst", "src", "core", "size"):
        c = pr.col(name)
        if c.size:
            lo_v, hi_v = int(c.min()), int(c.max())
            if lo_v < 0 or hi_v >= 32:
                raise DecodeUnsupported(
                    f"register operand {name}={lo_v if lo_v < 0 else hi_v}"
                    " outside G0..G31")
            hi = max(hi, hi_v)
    return _bucket(hi + 1, lo=8)


def _device_cols(pr: _Prep) -> Dict[str, np.ndarray]:
    """Pad the prep columns to the shape bucket (host-side numpy)."""
    n, nb = pr.n, _bucket(pr.n)

    def pad(x: np.ndarray, fill: int = 0) -> np.ndarray:
        x = x.astype(np.int64, copy=False)
        if nb == n:
            return x
        out = np.full(nb, fill, dtype=np.int64)
        out[:n] = x
        return out

    cols = {"op": pad(pr.op), "starts": pad(pr.starts, fill=n)}
    for name in _COL_NAMES:
        cols[name] = pad(pr.col(name))
    return cols


def _call_exec(fn: Any, sc: Tuple[np.ndarray, ...],
               cols: Dict[str, np.ndarray], n: int
               ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """Upload, run (under x64), download, and un-pad one device call."""
    import warnings

    import jax
    import jax.numpy as jnp
    up = (sum(v.nbytes for v in cols.values())
          + sum(np.asarray(x).nbytes for x in sc))
    with span("repro.sim.device_call", rows=n, bucket=len(cols["op"]),
              regs=_EXEC_REGS[fn], machines=int(np.size(sc[0])),
              bytes_up=up) as sp, \
            jax.enable_x64(True), warnings.catch_warnings():
        # donation is best-effort: a couple of int64 columns have no
        # matching output shape — harmless, not worth a user warning
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        dev_cols = {k: jnp.asarray(v) for k, v in cols.items()}
        dev_sc = tuple(jnp.asarray(x) for x in sc)
        lat, res = fn(dev_sc, dev_cols)
        lat = np.asarray(lat)
        res = {k: np.asarray(v) for k, v in res.items()}
        sp.set_metadata(bytes_down=lat.nbytes
                        + sum(v.nbytes for v in res.values()))
        res = {k: v[:n] for k, v in res.items()}
    return lat[..., :n], res


def _finish_from_device(out: _DecodedStage, pr: _Prep, dec: StageDecoder,
                        m: MachineModel, ilat: np.ndarray,
                        res: Dict[str, np.ndarray]) -> None:
    """Numpy back half: float latencies, event ledgers, boundary items,
    replay plan.

    ``ilat`` holds the device's int64 vector / MVM latencies; the rest
    are priced here.  Every expression is copied verbatim from the
    numpy engine's ``decode_stage`` (same dtypes, same `.sum()` order),
    so the totals are bit-identical given identical per-instruction
    inputs.
    """
    with span("repro.sim.finish"):
        _finish(out, pr, dec, m, ilat, res)


def _finish(out: _DecodedStage, pr: _Prep, dec: StageDecoder,
            m: MachineModel, ilat: np.ndarray,
            res: Dict[str, np.ndarray]) -> None:
    op, kind, col = pr.op, pr.kind, pr.col
    ev_tot = [0.0] * 4
    ev_cnt = [0] * 4
    lat = dec.clat[op].copy()
    dpos = np.flatnonzero((kind == _K_VEC) | (kind == _K_MVM))
    lat[dpos] = ilat[dpos]                # int64 -> float64, exact < 2^53

    # ---- S_LD / S_ST ledger traffic (4 B words) --------------------
    n_mem = int(((op == dec.id_sld) | (op == dec.id_sst)).sum())
    ev_tot[0] += 4.0 * n_mem
    ev_cnt[0] += n_mem

    # ---- vector ops ------------------------------------------------
    vpos = np.flatnonzero(kind == _K_VEC)
    if len(vpos):
        n_el = (np.maximum(res["vlen"][vpos], 1)
                * np.maximum(res["vrep"][vpos], 1))
        esz = np.where(col("flags")[vpos] & _I8_FLAG, 1, 4)
        ev_tot[0] += float((n_el * esz * 2).sum())
        ev_tot[3] += float(n_el.sum())
        ev_cnt[0] += len(vpos)
        ev_cnt[3] += len(vpos)

    # ---- CIM_LOAD --------------------------------------------------
    lpos = np.flatnonzero(kind == _K_WLOAD)
    if len(lpos):
        rows = col("rows")[lpos]
        lat[lpos] = m.weight_load_cycles_array(rows)
        nlen = np.maximum(res["nlen"][lpos], 1)
        wl = float((rows * nlen).sum())
        ev_tot[0] += wl
        ev_tot[1] += wl
        ev_cnt[0] += len(lpos)
        ev_cnt[1] += len(lpos)

    # ---- CIM_MVM ---------------------------------------------------
    mpos = np.flatnonzero(kind == _K_MVM)
    if len(mpos):
        rep = col("rep")[mpos]
        mask = ((res["mask_lo"][mpos] & 0xFFFF)
                | (res["mask_hi"][mpos] << 16))
        act = res["loaded"][mpos] & mask
        active = np.zeros(len(mpos), dtype=np.int64)
        for b in range(32):
            active += (act >> b) & 1
        ev_tot[2] += float((rep * active).sum() * m.macros_per_group)
        seg = res["seg_in"][mpos] + res["seg_out"][mpos]
        ev_tot[0] += float((rep * seg).sum())
        ev_cnt[0] += len(mpos)
        ev_cnt[2] += len(mpos)

    # ---- BCAST: sender-side injection occupancy --------------------
    bcast = np.flatnonzero(kind == _K_BCAST)
    if len(bcast):
        lat[bcast] = m.send_issue_cycles_array(res["size"][bcast])

    # ---- boundary items --------------------------------------------
    bitems: Dict[int, tuple] = {}
    for tag in (_K_SEND, _K_RECV):
        kpos = np.flatnonzero(kind == tag)
        for p, c, s, st in zip(kpos.tolist(),
                               res["core"][kpos].tolist(),
                               res["size"][kpos].tolist(),
                               res["chan"][kpos].tolist()):
            bitems[p] = (tag, c, s, st)
    for tag in (_K_GLD, _K_GST):
        kpos = np.flatnonzero(kind == tag)
        for p, s in zip(kpos.tolist(), res["size"][kpos].tolist()):
            bitems[p] = (tag, s)
    sync = np.flatnonzero(kind == _K_SYNC)
    for p, b in zip(sync.tolist(), col("barrier")[sync].tolist()):
        bitems[p] = (_K_SYNC, b)

    _finish_decode(out, pr, dec.unit[op], lat, bitems, ev_tot, ev_cnt)


# ---------------------------------------------------------------------------
# Decoders
# ---------------------------------------------------------------------------


class JaxStageDecoder:
    """Single-machine JAX decode: drop-in for :class:`StageDecoder`.

    Wraps a numpy :class:`StageDecoder` for the machine-independent prep
    (pack / dead-code / unroll split) and per-op tables, and replaces
    the dataflow + latency passes with the jitted device call.
    """

    def __init__(self, isa: Isa, m: MachineModel) -> None:
        self.isa = isa
        self.m = m
        with span("repro.sim.decode_prep", machines=1):
            self.npdec = StageDecoder(isa, m)
            self._sc = _scalar_row(m.timing_constants())

    def decode_stage(self, programs: Dict[int, Program]) -> _DecodedStage:
        out = _DecodedStage()
        with span("repro.sim.decode_prep", machines=1) as sp:
            pr = self.npdec._prep(programs)
            sp.set_metadata(rows=pr.n)
            out.n_prog = pr.n_prog
            for cid in pr.empty:
                out.items[cid] = [(_END,)]
            for cid, prog in pr.unroll:
                self.npdec.unroll_decode(prog, cid, out)
            if not pr.cids:
                return out
            fn, _ = _exec_for(self.npdec, _reg_bucket(pr))
            cols = _device_cols(pr)
        lat, res = _call_exec(fn, self._sc, cols, pr.n)
        _finish_from_device(out, pr, self.npdec, self.m, lat, res)
        return out


class FleetStageDecoder:
    """Batched decode of one stage for a whole fleet of machines.

    One prep, one vmapped device call over the stacked
    :class:`MachineTables`, then one cheap numpy finish per machine —
    the replay plans are exactly what each machine's own
    ``Simulator(engine="jax")`` would build.
    """

    def __init__(self, isa: Isa, machines: List[MachineModel]) -> None:
        self.isa = isa
        self.machines = list(machines)
        with span("repro.sim.decode_prep", machines=len(self.machines)):
            self.npdecs = [StageDecoder(isa, m) for m in self.machines]
        self.tables = MachineTables.stack(self.machines)
        self._seen: set = set()     # tables sent while recording

    def _mark_repeat(self, cols: Dict[str, np.ndarray]) -> None:
        """While a profiler records: mark, with an instant
        ``repro.sim.device_call.repeat`` span before its call, a stage
        table (digest of every column) this decoder already sent (a
        loop step's stage decoded again)."""
        if not recording():
            return
        h = hashlib.blake2b(digest_size=16)
        for k in sorted(cols):
            h.update(k.encode())
            h.update(np.ascontiguousarray(cols[k]).tobytes())
        fp = h.digest()
        if fp in self._seen:
            with span("repro.sim.device_call.repeat"):
                pass
        self._seen.add(fp)

    def prep(self, programs: Dict[int, Program]) -> _Prep:
        """Machine-independent front half (cacheable by the caller)."""
        with span("repro.sim.decode_prep",
                  machines=len(self.machines)) as sp:
            pr = self.npdecs[0]._prep(programs)
            sp.set_metadata(rows=pr.n)
        return pr

    def decode_stage(self, programs: Dict[int, Program],
                     prep: Optional[_Prep] = None) -> List[_DecodedStage]:
        pr = prep if prep is not None else self.prep(programs)
        n = len(self.machines)
        lat = res = None
        if pr.cids:
            with span("repro.sim.decode_prep", rows=pr.n, machines=n):
                _, fleet_fn = _exec_for(self.npdecs[0], _reg_bucket(pr))
                cols = _device_cols(pr)
            self._mark_repeat(cols)
            lat, res = _call_exec(fleet_fn, self.tables.arrays, cols, pr.n)
        outs: List[_DecodedStage] = []
        for i, (m, dec) in enumerate(zip(self.machines, self.npdecs)):
            out = _DecodedStage()
            out.n_prog = dict(pr.n_prog)
            for cid in pr.empty:
                out.items[cid] = [(_END,)]
            if pr.unroll:
                with span("repro.sim.decode_prep", rows=pr.n, machines=1):
                    for cid, prog in pr.unroll:
                        dec.unroll_decode(prog, cid, out)
            if pr.cids:
                _finish_from_device(out, pr, dec, m, lat[i], res)
            outs.append(out)
        return outs


def run_stage(sim: Any, sp: Any) -> Optional[Tuple[float, Dict[str, float],
                                                   Dict[str, float], int]]:
    """JAX-engine counterpart of :func:`vectorsim.run_stage`.

    Decode on device, replay with the shared
    :func:`vectorsim.replay_stage`; ``None`` when the stage is outside
    the decodable subset (scalar-interpreter fallback, as ever).
    """
    dec = getattr(sim, "_jdecoder", None)
    if dec is None or dec.isa is not sim.isa:
        dec = sim._jdecoder = JaxStageDecoder(sim.isa, sim.m)
    try:
        ds = dec.decode_stage(sp.programs)
    except DecodeUnsupported:
        return None
    return replay_stage(sim, sp, ds)
