"""The control of the check: the plain reference computed in bfloat16, the
nearest precision below the float32 the configurations state, put in the
system's place. It has to fail a limit; the benchmark's own runs never
run it (`limits.py` reads it on the chip, a test at a small size)."""
from __future__ import annotations

import jax.numpy as jnp


def served_in_bfloat16(cell, config: dict, data, rows) -> dict:
    """One request as the control would serve it."""
    out = [cell.objective.reference(*data, float(config["l2"]), row,
                                    int(cell.mix["epochs"]),
                                    float(cell.mix["drop_prob"]),
                                    dtype=jnp.bfloat16)
           for row in rows]
    return {"rows": rows, "w": [w for w, _ in out],
            "losses": [losses for _, losses in out]}
