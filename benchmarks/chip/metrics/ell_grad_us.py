"""Device time of the ELL objective's per-sample gradients in one inner
step, in microseconds (device trace): the own time of the ops in scope
``ell_grad`` (the gathers, margins and scatters of the step's two
gradients, at the read iterate and at the snapshot) over the inner
steps, the `svrg_update` kernel calls, in the traced window. None off the
chip; see `chipbench.objective_scopes.device_time`."""
from chipbench import objective_scopes
from objectives import logreg_ell


def read(r):
    s = objective_scopes.device_time(r, logreg_ell.ELL_GRAD, per_step=True)
    return None if s is None else 1e6 * s
