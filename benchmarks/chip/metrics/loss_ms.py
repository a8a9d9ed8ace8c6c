"""Device time of one fixed-order loss evaluation, in milliseconds
(device trace): the own time of the ops in scope ``loss`` over its calls
in the traced window (the loss at w0 and after each epoch). None for a
program that names no scopes; see `chipbench.scopes.device_time`."""
from chipbench import scopes


def read(r):
    s = scopes.device_time(r, scopes.LOSS, per_step=False)
    return None if s is None else 1e3 * s
