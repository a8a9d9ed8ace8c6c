"""Median over the window's requests of the time the HTTP tier added: the
client's latency less the server's own span of the request, from its
`submit` start to the end of the `result` wait that answered it (program
spans on the clients' clock)."""
import statistics

from chipbench import spans


def read(r):
    wire = []
    for req, trace in zip(r.done, r.spans):
        sub, res = spans.first(trace, "submit"), spans.last(trace, "result")
        if sub and res:
            served = spans.end(res) - sub["start_s"]
            wire.append((req["t_done"] - req["t_submit"] - served) * 1000.0)
    return statistics.median(wire) if wire else None
