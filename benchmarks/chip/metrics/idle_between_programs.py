"""Share of the traced window in which no program ran on the device: 1 -
(union of the program runs) / (traced span), averaged over the cell's
chips (device trace; runs as `chipbench.scopes` reads them). The rest of
`device_idle` lies inside program runs, between a program's own ops. On
a TPU a trace without the cell's device planes or any program run in the
window is an error."""
from chipbench import scopes


def read(r):
    st = scopes.of(r)
    if st is None or st.chips_seen < r.cell.chips or st.program_s <= 0:
        if r.on_chip:
            raise RuntimeError(
                f"device trace: {st.chips_seen if st else 0} of "
                f"{r.cell.chips} device plane(s), program runs "
                f"{st.program_s if st else 0} s in the traced window")
        return None
    return 100.0 * (1.0 - st.program_s / st.window_s)
