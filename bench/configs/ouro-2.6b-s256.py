"""Plain INT8 reference of this chip's cut of Ouro-2.6B, built from
``ouro-2.6b-s256.json``.

The LoopLM forward (arXiv:2510.25741; the published ``config.json``)
restricted to this chip: its layers (45-48, named ``l44``..``l47``) run
``total_ut_steps`` times on a state of ``seq`` tokens, each step's
final RMSNorm output feeding the next step.  Per layer, with sandwich
norms and the model's equations::

    x = N1(h);  q, k = rope(x Wq), rope(x Wk);  v = x Wv
    a = softmax(q_h k_h^T / sqrt(128) + M) v_h  (16 heads of 128;
        M the causal mask: token i attends to tokens j <= i)
    h = h + N2(a Wo);  x = N3(h)
    h = h + N4((silu(x Wg) * (x Wu)) Wd)

and after the last layer of the stack ``s = Nf(h)`` (the step's state)
and the exit gate ``sigmoid(s We)``.  Departures, all listed under the
configuration's ``assumed``: INT8 weights and activations with INT32
accumulation, each matmul requantized by a power-of-two shift (the
attention scale is folded into the scores' shift), integer LUT softmax,
SiLU and sigmoid, integer RMSNorm (exact integer square root) followed
by a saturating int8 gain, RoPE with Q14 cos/sin tables, and the gated
product requantized by a shift of its own.

Tensors are named after the system's groups: ``s<t>.l<i>.<op>`` for
``q``, ``k`` (after RoPE), ``v``, ``scores`` (after the masked softmax;
rows are tokens, each head a segment of ``seq``), ``ctx``, ``o`` (N3 of
the stream), ``gate``, ``up`` (the gated product), ``down`` (N1 of the
next layer's input, or the state after the last step) and
``s<t>.exit``.  The shifts are shared by the loop steps and sized on
step 0.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

W_RANGE = (-6, 7)
GAIN_RANGE = (1, 3)
STATE_RANGE = (-8, 8)
TARGET_STD = 32
LN_GAIN = 48
ROPE_FRAC = 14

_v = np.arange(-128, 128, dtype=np.float64)
_sig = 1.0 / (1.0 + np.exp(-_v / 16.0))
SILU = np.clip(np.round(_v * _sig), -128, 127).astype(np.int64)
SIGMOID = np.clip(np.round(127.0 * _sig), 0, 127).astype(np.int64)
EXP2 = np.round(2.0 ** 14 * 2.0 ** (-np.arange(256, dtype=np.float64)
                                    / 16.0)).astype(np.int64)
del _v, _sig

MATMULS = ("q", "k", "v", "o", "gate", "up", "down")


def _layers_here(cfg: Dict[str, Any]) -> List[int]:
    n = cfg["num_hidden_layers"]
    total = len(cfg["layer_types"])
    return list(range(total - n, total))


def layers(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Every matmul of one forward, in order, with (M, K, N) and MACs."""
    d, f, seq = cfg["hidden_size"], cfg["intermediate_size"], cfg["seq"]
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    shapes = {"q": (d, d), "k": (d, d), "v": (d, d), "o": (d, d),
              "gate": (d, f), "up": (d, f), "down": (f, d)}
    out = []
    for t in range(cfg["total_ut_steps"]):
        for li in _layers_here(cfg):
            for op in ("q", "k", "v", "scores", "ctx", "o", "gate", "up",
                       "down"):
                if op == "scores":
                    m, k, n = seq, hd, seq
                elif op == "ctx":
                    m, k, n = seq, seq, hd
                else:
                    (k, n), m = shapes[op], seq
                g = heads if op in ("scores", "ctx") else 1
                out.append({"name": f"s{t}.l{li}.{op}", "m": m, "kdim": k,
                            "n": n, "groups": g, "macs": m * k * n * g})
        out.append({"name": f"s{t}.exit", "m": seq, "kdim": d, "n": 1,
                    "groups": 1, "macs": seq * d})
    return out


def make_params(cfg: Dict[str, Any], rng: np.random.Generator
                ) -> Dict[str, np.ndarray]:
    """Seeded int8 ``(K, N)`` matrices ``l<i>.<op>`` and ``exit``, and
    int8 RMSNorm gains ``l<i>.n1``..``n4`` and ``final_norm``."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    shapes = {"q": (d, d), "k": (d, d), "v": (d, d), "o": (d, d),
              "gate": (d, f), "up": (d, f), "down": (f, d)}
    p: Dict[str, np.ndarray] = {}
    for li in _layers_here(cfg):
        for op in MATMULS:
            p[f"l{li}.{op}"] = rng.integers(*W_RANGE, shapes[op],
                                            dtype=np.int8)
        for nrm in ("n1", "n2", "n3", "n4"):
            p[f"l{li}.{nrm}"] = rng.integers(*GAIN_RANGE, d).astype(np.int8)
    p["final_norm"] = rng.integers(*GAIN_RANGE, d).astype(np.int8)
    p["exit"] = rng.integers(*W_RANGE, (d, 1), dtype=np.int8)
    return p


def make_state(cfg: Dict[str, Any], rng: np.random.Generator) -> np.ndarray:
    """The state entering the chip's first layer: int8 ``(seq, d)``."""
    return rng.integers(*STATE_RANGE, (cfg["seq"], cfg["hidden_size"])
                        ).astype(np.int8)


def _matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Exact int8 x int8 -> int64 product (float64 holds every sum)."""
    return np.rint(a.astype(np.float64) @ b.astype(np.float64)
                   ).astype(np.int64)


def _requant(v: np.ndarray, shift: int) -> np.ndarray:
    den = 1 << shift
    return np.clip((v + (den >> 1)) // den, -128, 127).astype(np.int8)


def _shift_for(v: np.ndarray) -> int:
    std = max(1.0, float(np.std(v)))
    return min(30, max(0, round(math.log2(std / TARGET_STD))))


def rmsnorm(x: np.ndarray, gain: np.ndarray) -> np.ndarray:
    """Per row: ``round(48·x / rms(x))`` with the rms from an exact
    integer square root, then the saturating int8 gain."""
    n = x.shape[-1]
    d = n * x.astype(np.int64)
    ss = (d * d).sum(axis=-1)
    r = np.array([math.isqrt(int(v) // n) + 1 for v in ss],
                 dtype=np.int64)[:, None]
    y = np.clip((2 * LN_GAIN * d + r) // (2 * r), -128, 127)
    return np.clip(y * gain.astype(np.int64)[None, :], -128,
                   127).astype(np.int8)


def rope(x: np.ndarray, hd: int, theta: float) -> np.ndarray:
    """Rotate-half RoPE on every head, position = row: Q14 cos/sin of
    ``p·theta^(-2i/hd)``, products rounded half up from Q14."""
    rows, half = x.shape[0], hd // 2
    freq = float(theta) ** (-np.arange(half, dtype=np.float64) * 2.0 / hd)
    ang = np.arange(rows, dtype=np.float64)[:, None] * freq[None, :]
    one = float(1 << ROPE_FRAC)
    c = np.round(one * np.cos(ang)).astype(np.int64)
    s = np.round(one * np.sin(ang)).astype(np.int64)
    out = np.empty_like(x)
    for h0 in range(0, x.shape[1], hd):
        a = x[:, h0:h0 + half].astype(np.int64)
        b = x[:, h0 + half:h0 + hd].astype(np.int64)
        v = np.concatenate([a * c - b * s, b * c + a * s], axis=1)
        out[:, h0:h0 + hd] = np.clip(
            (v + (1 << (ROPE_FRAC - 1))) >> ROPE_FRAC, -128, 127)
    return out


def causal_softmax(x: np.ndarray) -> np.ndarray:
    """Scores ``(query, head, key)``; query ``i`` attends to keys
    ``j <= i`` only: over those, ``round(127·e / Σe)`` with ``e =
    2^14·2^(-(max-x)/16)`` from the table; masked keys read 0."""
    xi = x.astype(np.int64)
    n = x.shape[-1]
    keep = np.arange(n)[None, None, :] <= np.arange(x.shape[0])[:, None,
                                                                None]
    mx = np.where(keep, xi, -128).max(axis=-1, keepdims=True)
    e = np.where(keep, EXP2[np.clip(mx - xi, 0, 255)], 0)
    s = e.sum(axis=-1, keepdims=True)
    return np.clip((127 * e + (s >> 1)) // s, 0, 127).astype(np.int8)


def sat_add(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.clip(a.astype(np.int16) + b.astype(np.int16),
                   -128, 127).astype(np.int8)


def forward(cfg: Dict[str, Any], p: Dict[str, np.ndarray],
            shifts: Dict[str, int], x: np.ndarray,
            calibrating: bool = False) -> Dict[str, np.ndarray]:
    """The state ``x`` through every loop step; every named tensor
    comes back.  With ``calibrating``, each shift is chosen from its
    accumulator's standard deviation the first time the pass reaches
    it (step 0) and written into ``shifts``."""
    seq, heads, hd = cfg["seq"], cfg["num_attention_heads"], cfg["head_dim"]
    theta = cfg["rope_theta"]
    here = _layers_here(cfg)
    holds_last = here[-1] == len(cfg["layer_types"]) - 1
    t_out: Dict[str, np.ndarray] = {}

    def shift(key: str, acc: np.ndarray) -> int:
        if calibrating and key not in shifts:
            shifts[key] = _shift_for(acc)
        return shifts[key]

    def lin(key: str, a: np.ndarray) -> np.ndarray:
        acc = _matmul(a, p[key])
        return _requant(acc, shift(key, acc))

    h = x
    xn = rmsnorm(h, p[f"l{here[0]}.n1"])
    steps = cfg["total_ut_steps"]
    for t in range(steps):
        for li in here:
            P, L = f"s{t}.l{li}", f"l{li}"
            q = t_out[f"{P}.q"] = rope(lin(f"{L}.q", xn), hd, theta)
            k = t_out[f"{P}.k"] = rope(lin(f"{L}.k", xn), hd, theta)
            v = t_out[f"{P}.v"] = lin(f"{L}.v", xn)
            sc = np.empty((seq, heads * seq), dtype=np.int64)
            for hh in range(heads):
                cols = slice(hh * hd, (hh + 1) * hd)
                sc[:, hh * seq:(hh + 1) * seq] = _matmul(q[:, cols],
                                                         k[:, cols].T)
            sc8 = _requant(sc, shift(f"{L}.scores", sc))
            pr = t_out[f"{P}.scores"] = causal_softmax(
                sc8.reshape(seq, heads, seq)).reshape(seq, heads * seq)
            cx = np.empty((seq, heads * hd), dtype=np.int64)
            for hh in range(heads):
                cx[:, hh * hd:(hh + 1) * hd] = _matmul(
                    pr[:, hh * seq:(hh + 1) * seq],
                    v[:, hh * hd:(hh + 1) * hd])
            c8 = t_out[f"{P}.ctx"] = _requant(cx, shift(f"{L}.ctx", cx))
            h1 = sat_add(rmsnorm(lin(f"{L}.o", c8), p[f"{L}.n2"]), h)
            x3 = t_out[f"{P}.o"] = rmsnorm(h1, p[f"{L}.n3"])
            g8 = t_out[f"{P}.gate"] = lin(f"{L}.gate", x3)
            u8 = lin(f"{L}.up", x3)
            prod = SILU[g8.astype(np.int64) + 128] * u8.astype(np.int64)
            y = t_out[f"{P}.up"] = _requant(prod,
                                            shift(f"{L}.glu", prod))
            h = sat_add(rmsnorm(lin(f"{L}.down", y), p[f"{L}.n4"]), h1)
            if holds_last and li == here[-1]:
                h = rmsnorm(h, p["final_norm"])
                acc = _matmul(h, p["exit"])
                t_out[f"s{t}.exit"] = SIGMOID[
                    _requant(acc, shift("exit", acc)).astype(np.int64)
                    + 128].astype(np.int8)
            if t + 1 < steps or li != here[-1]:
                nxt = here[0] if li == here[-1] else li + 1
                xn = rmsnorm(h, p[f"l{nxt}.n1"])
            t_out[f"{P}.down"] = xn if (t + 1 < steps or li != here[-1]) \
                else h
    t_out["state"] = h
    return t_out


def calibrate(cfg: Dict[str, Any], p: Dict[str, np.ndarray],
              x: np.ndarray) -> Dict[str, int]:
    """Shifts ``l<i>.<op>`` (each matmul), ``l<i>.glu`` (the gated
    product) and ``exit``, sized on step 0 so that each requantized
    value's standard deviation comes near ``TARGET_STD``."""
    shifts: Dict[str, int] = {}
    forward(cfg, p, shifts, x, calibrating=True)
    return shifts


def nonzero_share(t: Dict[str, np.ndarray]) -> float:
    """Share of non-zero elements of the final state."""
    s = t["state"]
    return float(np.count_nonzero(s)) / s.size


def system_params(p: Dict[str, np.ndarray], shifts: Dict[str, int],
                  cg: Any, quant: Any) -> Dict[str, Dict[int, Any]]:
    """The reference's parameters keyed as the system under test takes
    them, read off its condensed graph ``cg`` by each op's
    ``attrs['param']``: ``weights`` and ``quant`` (built with the
    system's ``quant(scale, shift, mul_shift)``) per owning group,
    ``norms`` per owning RMSNorm op."""
    ops = cg.source.ops
    weights: Dict[int, np.ndarray] = {}
    qp: Dict[int, Any] = {}
    norms: Dict[int, np.ndarray] = {}
    for g in cg:
        key = ops[g.anchor].attrs["param"]
        if g.owner is None:
            if key in p:
                weights[g.idx] = p[key]
            glu = key[:-len("up")] + "glu" if key.endswith(".up") else None
            qp[g.idx] = quant(scale=1, shift=shifts[key],
                              mul_shift=shifts[glu] if glu else 0)
    for op in ops:
        if op.kind == "rmsnorm" and "owner" not in op.attrs:
            norms[op.idx] = p[op.attrs["param"]]
    return {"weights": weights, "quant": qp, "norms": norms}
