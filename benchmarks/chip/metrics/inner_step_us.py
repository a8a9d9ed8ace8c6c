"""Device time of one inner step of the epoch cores, in microseconds
(device trace): the own time of the ops in scope ``inner_step`` over the
inner steps, the `svrg_update` kernel calls, in the traced window. The
scan's loop control between steps is outside the scope. None for a
program that names no scopes; see `chipbench.scopes.device_time`."""
from chipbench import scopes


def read(r):
    s = scopes.device_time(r, scopes.INNER_STEP, per_step=True)
    return None if s is None else 1e6 * s
