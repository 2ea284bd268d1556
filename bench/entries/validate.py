"""Bit-exact validation: ``art.evaluate("func:pallas", weights=...,
biases=..., inputs=..., quant=..., check=True)`` on one fresh image per
request.

Every INT8 matmul of the model runs on the Pallas bit-serial kernel
(``jit_cim_mvm``) and the system's own numpy oracle re-runs the model
beside it, as a user's validation pays for it; the stage engine is not
on this path.  Set-up compiles the graph, draws the weights, biases and
one calibration image from the seed with the configuration's
reference, sizes every layer's shift on that image (the reference's
calibration, not the system's), and validates that image once, which
compiles the kernel on every layer's shape and runs the oracle's path
before the window.  The check runs the configuration's plain reference
on every image the window served and counts the group output elements
that differ.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["Entry"]

# suffixes of the op that ends a fused group -> the reference tensor
_SUFFIXES = (".bias", ".bn", ".relu")


def tensor_name(last_op: str) -> str:
    """The reference's name for the output of a group whose last op is
    ``last_op``: a layer's own output keeps the layer's name, a
    block's final relu names the block."""
    for suf in _SUFFIXES:
        if last_op.endswith(suf):
            return last_op[: -len(suf)]
    return last_op


class Entry:
    def __init__(self, cell: Any, rngs: Dict[str, np.random.Generator]
                 ) -> None:
        self.cfg = cell.config
        self.mix = cell.traffic
        self.rngs = rngs
        self.ref = cell.reference_module()
        self.art: Any = None
        self.macs = sum(ly["macs"] for ly in self.ref.layers(self.cfg))

    def setup(self) -> None:
        from repro import flow
        from repro.core.arch import default_chip
        from repro.core.codegen import QuantParams
        from repro.core.mapping import CostParams
        from repro.flow import CompileOptions

        cfg, rng = self.cfg, self.rngs["setup"]
        self.art = flow.compile(cfg["model"], default_chip(), CompileOptions(
            strategy=cfg["strategy"],
            params=CostParams(batch=cfg["validate_batch"]),
            workload_kw=cfg["workload_kw"], fidelity="analytic"))
        self.params = self.ref.make_params(cfg, rng)
        calib = self.ref.make_image(cfg, rng)
        self.shifts = self.ref.calibrate(cfg, self.params, calib)
        cg = self.art.cg
        ops = cg.source.ops
        self.weights: Dict[int, np.ndarray] = {}
        self.biases: Dict[int, np.ndarray] = {}
        self.quant: Dict[int, Any] = {}
        self.names: Dict[int, str] = {}
        for g in cg:
            layer = ops[g.anchor].name
            w = self.params[layer]
            self.weights[g.idx] = w.reshape(-1, w.shape[-1])
            if layer + ".bias" in self.params:
                self.biases[g.idx] = self.params[layer + ".bias"]
            self.quant[g.idx] = QuantParams(scale=1,
                                            shift=self.shifts[layer])
            self.names[g.idx] = tensor_name(ops[g.op_ids[-1]].name)
        self._evaluate(calib[None], check=True)

    def _images(self, payload: Dict[str, Any]) -> List[np.ndarray]:
        return [self.ref.make_image(self.cfg, np.random.default_rng(s))
                for s in payload["image_seeds"]]

    def _evaluate(self, x: np.ndarray, check: bool) -> Dict[int, np.ndarray]:
        return self.art.evaluate(
            "func:pallas", weights=self.weights, biases=self.biases,
            inputs=x, quant=self.quant, check=check).outputs

    def request(self, payload: Dict[str, Any]) -> Dict[int, np.ndarray]:
        return self._evaluate(np.stack(self._images(payload)), check=True)

    def work(self, payload: Dict[str, Any]) -> float:
        return float(self.macs * len(payload["image_seeds"]))

    def failed_in(self, outs: Dict[int, np.ndarray]) -> Optional[str]:
        return None

    def release(self) -> None:
        pass

    def kernel_calls(self) -> List[Tuple[int, int, int]]:
        """Each request's kernel calls, unpadded: (M, K, N), one per
        layer and image."""
        per = [(ly["m"], ly["kdim"], ly["n"])
               for ly in self.ref.layers(self.cfg)]
        return per * int(self.mix["images_per_request"])

    def check(self, done: List[Any], rng: np.random.Generator,
              control: bool = False) -> List[Dict[str, Any]]:
        if not done:
            return []
        bad = 0
        for req in done:
            for b, img in enumerate(self._images(req.payload)):
                want = self.ref.forward(self.cfg, self.params, self.shifts,
                                        img)
                if control:
                    got_t = self.ref.forward(self.cfg, self.params,
                                             self.shifts, img,
                                             operand_bits=4)
                for gid, name in self.names.items():
                    w = want[name]
                    if control:
                        g = got_t[name]
                    elif gid in req.result:
                        g = req.result[gid][b]
                    else:
                        bad += w.size
                        continue
                    bad += (w.size if g.shape != w.shape
                            else int(np.count_nonzero(g != w)))
        return [{"name": "mismatch_elems", "value": bad,
                 "limit": self.mix["limits"]["mismatch_elems"]}]
