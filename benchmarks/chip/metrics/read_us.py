"""Device time of the iterate read of one inner step, in microseconds
(device trace): the own time of the ops in scope ``read`` (`read_dispatch`
with every scheme's branch: under vmap all three run) over the inner steps
in the traced window. None for a program that names no scopes; see
`chipbench.scopes.device_time`."""
from chipbench import scopes


def read(r):
    s = scopes.device_time(r, scopes.READ, per_step=True)
    return None if s is None else 1e6 * s
