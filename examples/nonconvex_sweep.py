"""Nonconvex + pytree workloads through the sweep service.

The objective protocol (`repro.core.Objective`) decouples the async-SVRG
engine from the paper's logistic-regression workload. This example runs the
two bundled beyond-paper objectives end-to-end through the coalescing
`SweepService`:

  * `NonconvexLogistic` — logistic loss + smoothly-clipped (bounded,
    nonconvex) penalty on a libsvm-shaped set; params stay a flat vector.
  * `MLPObjective` (via `mlp_lm_objective`) — a tiny MLP language model on
    the deterministic synthetic-LM corpus; params are a NESTED PYTREE
    {embed, norm, w1, b1, w2}. The engine runs on the bit-exactly flattened
    vector and `SweepResult.final_params` rebuilds the tree.

Both requests land in ONE flush: the group key leads with the objective
fingerprint, so rows for different objectives coalesce in the same dispatch
window without ever sharing a compiled program. The MLP request addresses
its objective BY NAME through the registry (`register_objective`) — the
same addressing an HTTP client uses (`SweepSpec.objective`), so this demo
is one `SweepServer(...)` away from being served over the wire.

    PYTHONPATH=src python examples/nonconvex_sweep.py
"""
import numpy as np

from repro.core import (NonconvexLogistic, SweepSpec, mlp_lm_objective,
                        register_objective)
from repro.data.libsvm import make_synthetic_libsvm
from repro.service import SweepService
from repro.utils.compile_cache import enable_compile_cache


def main():
    ds = make_synthetic_libsvm("rcv1", scale=0.03)
    ncv = NonconvexLogistic(ds.X, ds.y, lam=1e-3, alpha=10.0)
    mlp = register_objective(
        "tiny-lm", mlp_lm_objective(n=32, vocab_size=16, seq_len=4,
                                    d_model=8, d_hidden=16))
    print(f"nonconvex logistic: n={ncv.n} p={ncv.p}   "
          f"tiny-lm: n={mlp.n} params={mlp.flat_dim}\n")

    # the service holds the nonconvex objective; the MLP request rides in
    # by registry name — one flush, two objectives, zero shared groups
    svc = SweepService(ncv, epochs=3)
    rid_ncv = svc.submit(
        [SweepSpec(scheme="inconsistent", step_size=s, tau=3, num_threads=4)
         for s in (0.5, 1.0, 2.0)], tenant="nonconvex")
    rid_mlp = svc.submit(
        [SweepSpec(scheme="unlock", step_size=s, tau=2, num_threads=4,
                   inner_steps=mlp.n, objective="tiny-lm")
         for s in (0.05, 0.1)], tenant="lm")
    svc.flush()

    res = svc.result(rid_ncv)
    print("nonconvex clipped-penalty logistic (flat params):")
    for c, spec in enumerate(res.specs):
        print(f"  step={spec.step_size:3.1f}: loss "
              f"{res.histories[c, 0]:.4f} -> {res.histories[c, -1]:.4f}")

    res = svc.result(rid_mlp)
    print("\ntiny MLP language model (pytree params, same engine):")
    for c, spec in enumerate(res.specs):
        params = res.final_params(c)             # dict rebuilt bit-exactly
        norms = {k: float(np.linalg.norm(v)) for k, v in params.items()}
        print(f"  step={spec.step_size:4.2f}: loss "
              f"{res.histories[c, 0]:.4f} -> {res.histories[c, -1]:.4f}  "
              f"|embed|={norms['embed']:.3f} |w2|={norms['w2']:.3f}")

    stats = svc.stats()
    print(f"\none flush: {stats.rows_submitted} rows, "
          f"{stats.groups_dispatched} dispatched groups "
          "(objectives never share a group)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
