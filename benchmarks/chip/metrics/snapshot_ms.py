"""Device time of one snapshot pass (the full gradient at the epoch's
start), in milliseconds (device trace): the own time of the ops in scope
``snapshot`` over its calls in the traced window. None for a program
that names no scopes; see `chipbench.scopes.device_time`."""
from chipbench import scopes


def read(r):
    s = scopes.device_time(r, scopes.SNAPSHOT, per_step=False)
    return None if s is None else 1e3 * s
