"""The one generator every traffic mix goes through.

A mix is a JSON file under ``traffic/``. Every tenant is a closed loop:
it sends its next request once the previous one has come back. Its keys:

- ``epochs``: the epoch budget of every request;
- ``drop_prob``: the unlock scheme's write-drop share the service runs;
- ``flush``: the flush daemon's `FlushPolicy` (``max_rows``,
  ``max_delay_ms``);
- ``check_per_kind``: how many of the window's requests of each kind (the
  algorithm and read scheme of each row) the reference recomputes after
  the window;
- ``trace_seconds``: how long the profiler records in a ``--trace 1`` run;
- ``tenants``: a list of ``{"name", "count"?, "rows"}``. ``count`` repeats
  a tenant; ``rows`` are the spec rows of each of its requests. A row
  field is a plain value, ``{"cycle": [...]}`` (request k takes entry
  k mod len) or ``{"choice": [...]}`` (drawn per request from the seed).

Every request's seeds and choices come from the run's seed, the tenant's
place in the list and the request's number, so a seed fixes the work
whatever the timing, and every seed gives the same sizes.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

SEED_BOUND = 2**31 - 1       # request seeds enter the system as int32


def tenants(mix: dict) -> List[dict]:
    """The mix's tenants with ``count`` expanded, in list order."""
    out = []
    for t in mix["tenants"]:
        count = int(t.get("count", 1))
        for c in range(count):
            name = t["name"] if count == 1 else f"{t['name']}.{c}"
            out.append({"name": name, "rows": t["rows"]})
    return out


def _field(value, k: int, rng: np.random.Generator):
    if isinstance(value, dict) and "cycle" in value:
        return value["cycle"][k % len(value["cycle"])]
    if isinstance(value, dict) and "choice" in value:
        return value["choice"][int(rng.integers(len(value["choice"])))]
    return value


def request_rows(tenant: dict, tenant_index: int, k: int,
                 run_seed: int) -> List[Dict]:
    """The spec rows of request ``k`` of a tenant; ``k = -1`` is the
    warm-up request, whose seeds no request of the window shares."""
    rng = np.random.default_rng([int(run_seed), tenant_index, k + 1])
    rows = []
    for template in tenant["rows"]:
        row = {name: _field(template[name], k, rng)
               for name in sorted(template)}
        row["seed"] = int(rng.integers(SEED_BOUND))
        rows.append(row)
    return rows
