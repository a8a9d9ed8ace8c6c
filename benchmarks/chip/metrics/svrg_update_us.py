"""Mean device time of one call of the `svrg_update` kernel, in
microseconds (device trace): the summed durations of its calls in the
traced window over their number. On a TPU a trace with no call of the
kernel is an error.

Its share of a roofline is not reported: on a v5e the compiler keeps the
kernel's operands in on-chip memory (layouts ``S(1)`` in the trace), so
the bytes do not cross HBM and HBM bandwidth gives no least time."""


def read(r):
    calls = r.trace.kernel_calls("svrg_update") if r.trace else []
    if not calls:
        if r.on_chip:
            raise RuntimeError("device trace: no svrg_update call")
        return None
    return 1e6 * sum(calls) / len(calls)
