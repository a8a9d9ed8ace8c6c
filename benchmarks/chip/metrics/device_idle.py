"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / (traced span), averaged
over the chips the cell uses (device trace). On a TPU a trace without the
cell's device planes or without any operation in the window is an error."""


def read(r):
    trace = r.trace
    if trace is None or trace.chips_seen < r.cell.chips or trace.busy_s <= 0:
        if r.on_chip:
            raise RuntimeError(
                f"device trace: {trace.chips_seen if trace else 0} of "
                f"{r.cell.chips} device plane(s), busy "
                f"{trace.busy_s if trace else 0} s in the traced window")
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
