"""Whether what the timed path served is correct.

After the window closes, a sample of the requests it completed, drawn from
the run's seed (``check_per_kind`` of each kind of request the window
served: the algorithm and read scheme of each of its rows), is recomputed
by the configuration's plain reference in float32 from the same data. Two
numbers are compared, each the worst over the sampled requests:

- ``w_gap``: max_j |w_served - w_ref| / max_j |w_ref|, the final iterate's
  largest gap against the reference's largest coordinate;
- ``loss_gap``: max over epochs of |loss_served - loss_ref| / loss_ref.

Each has its own limit in the configuration's file (``limits``). PERF.md
gives the readings each limit was set from: the largest over a dozen seeds
of sound runs, and the smallest the control gives (the same reference
computed in bfloat16, in the system's place).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def kind(req: dict) -> tuple:
    """What a request asks the engine to run: the algorithm and read scheme
    of each of its rows, in order."""
    return tuple((r["algo"], r.get("scheme")) for r in req["rows"])


def sample(done: Sequence[dict], per_kind: int, seed: int) -> List[dict]:
    """``per_kind`` requests of each kind among ``done``, drawn from
    ``seed``, so that every kind of row the window served is checked."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    by_kind: Dict[tuple, List[dict]] = {}
    for req in done:
        by_kind.setdefault(kind(req), []).append(req)
    picked = []
    for k in sorted(by_kind, key=repr):
        reqs = by_kind[k]
        n = min(per_kind, len(reqs))
        picked += [reqs[i] for i in sorted(rng.choice(len(reqs), n,
                                                      replace=False))]
    return picked


def gaps(w_served, losses_served, w_ref, losses_ref) -> Dict[str, float]:
    w_served = np.asarray(w_served, np.float64)
    w_ref = np.asarray(w_ref, np.float64)
    ls = np.asarray(losses_served, np.float64)
    lr = np.asarray(losses_ref, np.float64)
    out = {"w_gap": float(np.max(np.abs(w_served - w_ref))
                          / np.max(np.abs(w_ref))),
           "loss_gap": float(np.max(np.abs(ls - lr) / np.abs(lr)))}
    # a non-finite answer is as wrong as an answer can be
    return {k: v if np.isfinite(v) else float("inf") for k, v in out.items()}


def compare(reqs: Sequence[dict], reference, data, config: dict,
            mix: dict) -> List[Dict]:
    """Each row's gaps, for every row of ``reqs``. Each request holds its
    ``rows`` and the served ``w`` and ``losses`` per row; ``reference`` is
    the objective kind's plain reference."""
    out = []
    for req in reqs:
        for r, row in enumerate(req["rows"]):
            w_ref, l_ref = reference(*data, float(config["l2"]), row,
                                     int(mix["epochs"]),
                                     float(mix["drop_prob"]))
            out.append({**row, **gaps(req["w"][r], req["losses"][r], w_ref,
                                      l_ref)})
    return out


def worst(rows: Sequence[Dict]) -> Dict[str, float]:
    return {name: max(r[name] for r in rows)
            for name in ("w_gap", "loss_gap")}
