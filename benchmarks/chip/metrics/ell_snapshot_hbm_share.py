"""Share of the chip's HBM bandwidth that one scatter-add snapshot
gradient of the ELL objective attains, in % (device trace): its least
bytes over the device time of one call of scope ``ell_snapshot``, over
``hbm_bytes_per_s`` of `peaks.json`. The least bytes (`least_bytes`) are
a lower bound, so the share passes 100% only where work is skipped. None
off the chip; see `chipbench.objective_scopes.device_time`."""
from chipbench import objective_scopes
from objectives import logreg_ell


def least_bytes(n: int, p: int, nnz: int) -> int:
    """Each int32 column id and float32 value read once, the float32
    labels read once, w read once and the float32 gradient written once."""
    return n * nnz * (4 + 4) + n * 4 + p * 4 + p * 4


def read(r):
    s = objective_scopes.device_time(r, logreg_ell.ELL_SNAPSHOT,
                                     per_step=False)
    if s is None:
        return None
    c = r.cell.config
    bytes_ = least_bytes(int(c["n"]), int(c["p"]), int(c["nnz_per_row"]))
    return 100.0 * bytes_ / s / r.peaks["hbm_bytes_per_s"]
