"""Host-clock time of the sweep engine's group dispatch per inner step:
the sum of the `dispatch` spans (which end once the group's results are on
the host) over the sum, across the groups they ran, of M~ x epochs, from
each dispatch's `execute` child (program spans)."""
from chipbench import spans


def read(r):
    total_s, steps = 0.0, 0
    for d in spans.unique(r.spans, "dispatch"):
        ex = spans.children(r.spans, d, "execute")
        if not ex:
            continue
        total_s += d["duration_ms"] / 1000.0
        steps += int(ex[0]["tags"]["total"]) * int(ex[0]["tags"]
                                                   ["group_epochs"])
    return 1e6 * total_s / steps if steps else None
