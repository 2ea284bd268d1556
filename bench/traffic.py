"""The one traffic generator: requests drawn from a mix's parameters.

A traffic mix is a JSON file under ``traffic/``.  Its keys:

* ``entry`` -- which module in ``entries/`` serves the requests;
* ``points_per_request`` and ``draws`` -- each request carries that
  many distinct design points; each point takes one value of every
  field in ``draws`` (a field's list of values), uniformly and
  independently, and a request never holds the same point twice;
* ``images_per_request`` -- each request carries that many image seeds,
  from which the configuration's reference draws the images;
* ``check_sample`` -- how many answers of a run the reference checks.

Requests run closed loop, one at a time, so the mix has no rate: the
next request starts when the last one has returned.  The stream is a
function of ``--seed`` alone; set-up draws (weights, calibration, the
warm-up request) and the checked sample come from streams of their own,
so the requests a run sends do not depend on how long set-up took.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List

import numpy as np

__all__ = ["streams", "requests", "draw_request", "draw_points"]

_MASK64 = (1 << 64) - 1


def streams(seed: int) -> Dict[str, np.random.Generator]:
    """Independent generators for the traffic, set-up and the check."""
    ss = np.random.SeedSequence(int(seed) & _MASK64)
    traffic, setup, check = ss.spawn(3)
    return {"traffic": np.random.default_rng(traffic),
            "setup": np.random.default_rng(setup),
            "check": np.random.default_rng(check)}


def draw_points(draws: Dict[str, List[Any]], n: int,
                rng: np.random.Generator) -> List[Dict[str, Any]]:
    """``n`` distinct points, each field drawn uniformly from its list."""
    fields = sorted(draws)
    space = 1
    for f in fields:
        space *= len(draws[f])
    if n > space:
        raise ValueError(f"{n} distinct points asked of a grid of {space}")
    seen = set()
    out: List[Dict[str, Any]] = []
    while len(out) < n:
        pick = tuple(draws[f][int(rng.integers(len(draws[f])))]
                     for f in fields)
        if pick not in seen:
            seen.add(pick)
            out.append(dict(zip(fields, pick)))
    return out


def draw_request(mix: Dict[str, Any],
                 rng: np.random.Generator) -> Dict[str, Any]:
    """One request of the mix's shapes."""
    req: Dict[str, Any] = {}
    if "points_per_request" in mix:
        req["points"] = draw_points(mix["draws"],
                                    int(mix["points_per_request"]), rng)
    if "images_per_request" in mix:
        req["image_seeds"] = [int(s) for s in rng.integers(
            0, 1 << 62, int(mix["images_per_request"]))]
    if not req:
        raise ValueError("a traffic mix needs points_per_request or "
                         "images_per_request")
    return req


def requests(mix: Dict[str, Any],
             rng: np.random.Generator) -> Iterator[Dict[str, Any]]:
    """The endless request stream of one run."""
    while True:
        yield draw_request(mix, rng)

