"""What a ``--trace 1`` run read, and the per-layer metrics taken from it.

The device trace is the JAX profiler's record of the window's first
``trace_seconds``. Its TPU planes give, per chip, the operations that ran
(line "XLA Ops"), from which the busy time (the union of their intervals
inside the traced span), the operations that took most time, the kernel
calls and the idle gaps are taken. A marker the benchmark writes into the
host's plane at a known `time.monotonic_ns()` puts the trace on the clock
of the service's spans, so each idle gap is named by the innermost span
the service had open then.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from chipbench import cells, spans

SYNC_MARKER = "chipbench.sync"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10


_ARRAY = re.compile(r"\b(\w+)\[([0-9,]*)\]")


def _signature(text: str):
    """(result, operands) of a custom call's HLO instruction, each array as
    (element type, shape); None where the text is no custom call."""
    head, sep, rest = text.partition(" custom-call(")
    result = _ARRAY.findall(head.partition(" = ")[2])
    if not sep or len(result) != 1:
        return None
    args = rest.partition("), custom_call_target")[0]

    def array(m):
        return m[0], tuple(int(d) for d in m[1].split(",") if d)
    return array(result[0]), [array(m) for m in _ARRAY.findall(args)]


def _is_svrg_update(name: str) -> bool:
    """A call of `svrg_update`'s Pallas kernel (``_update_kernel`` in
    ``kernels/svrg_update/kernel.py``). A v5e's trace names each operation
    by its HLO instruction and keeps nothing that names the kernel: the
    call is ``%closed_call.N = f32[...] custom-call(...)`` with
    ``custom_call_target="tpu_custom_call"`` (a Mosaic kernel) and empty
    ``kernel_metadata``. So it is matched by its operands: the (1, 1) step
    size, then u, g, g0 and gf, each of the result's float32 shape."""
    sig = _signature(name)
    if sig is None or 'custom_call_target="tpu_custom_call"' not in name:
        return False
    result, operands = sig
    return (result[0] == "f32" and len(operands) == 5
            and operands[0][0] == "f32" and operands[0][1][-2:] == (1, 1)
            and operands[1:] == [result] * 4)


KERNELS = {"svrg_update": _is_svrg_update}


@dataclasses.dataclass
class DeviceTrace:
    window_s: float                  # the traced span, host clock
    busy_s: float                    # union of operations, mean over chips
    ops: Dict[str, List[float]]      # name -> [calls, seconds], all chips
    calls: Dict[str, List[float]]    # kernel -> seconds of each call
    gaps: List[Tuple[float, float]]  # (start, monotonic s; length s)
    chips_seen: int = 0              # device planes reduced

    def kernel_calls(self, kernel: str) -> List[float]:
        return self.calls.get(kernel, [])


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def reduce_trace(planes, sync_mono_ns: int, stop_mono_ns: int,
                 chips: int) -> DeviceTrace:
    """Reduce profiler planes (``jax.profiler.ProfileData(...).planes``, or
    any objects with the same ``name``/``lines``/``events`` fields) to the
    window's device numbers. Event times are nanoseconds on the trace's
    clock; the sync marker maps them onto `time.monotonic_ns()`."""
    planes = list(planes)
    marker = next(e for p in planes if not DEVICE_PLANE.match(p.name)
                  for line in p.lines for e in line.events
                  if e.name == SYNC_MARKER)
    offset = sync_mono_ns - int(marker.start_ns)
    lo, hi = int(marker.start_ns), stop_mono_ns - offset
    ops: Dict[str, List[float]] = {}
    calls: Dict[str, List[float]] = {}
    matched: Dict[str, List[str]] = {}
    busy, gaps = [], []
    devices = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: int(DEVICE_PLANE.match(p.name).group(1)))
    for plane in devices[:chips]:
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                s = max(lo, int(e.start_ns))
                t = min(hi, int(e.start_ns + e.duration_ns))
                if t <= s:
                    continue
                intervals.append((s, t))
                # on a TPU the event's name is the op's whole HLO text
                agg = ops.setdefault(e.name.split(" = ", 1)[0], [0, 0.0])
                agg[0] += 1
                agg[1] += (t - s) / 1e9
                # an op repeats for every step: match each distinct op once
                if e.name not in matched:
                    matched[e.name] = [k for k, is_call in KERNELS.items()
                                       if is_call(e.name)]
                for kernel in matched[e.name]:
                    calls.setdefault(kernel, []).append((t - s) / 1e9)
        merged = _union(intervals)
        busy.append(sum(t - s for s, t in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [((edges[i] + offset) / 1e9, (edges[i + 1] - edges[i]) / 1e9)
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    return DeviceTrace(window_s=(hi - lo) / 1e9,
                       busy_s=sum(busy) / max(1, len(busy)), ops=ops,
                       calls=calls, gaps=sorted(gaps, key=lambda g: -g[1]),
                       chips_seen=len(busy))


def load_device_trace(trace_dir: Path, sync_mono_ns: int, stop_mono_ns: int,
                      chips: int) -> Optional[DeviceTrace]:
    from jax.profiler import ProfileData
    found = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    if not found:
        return None
    return reduce_trace(ProfileData.from_file(found[0]).planes,
                        sync_mono_ns, stop_mono_ns, chips)


@dataclasses.dataclass
class Readings:
    """Everything a per-layer reader may read (see ``metrics/``)."""
    cell: cells.Cell
    device: dict
    done: List[dict]                 # the requests completed in the window
    spans: List[dict]                # each done request's span tree
    trace: Optional[DeviceTrace]

    @property
    def on_chip(self) -> bool:
        """A TPU run, whose trace must hold what the device readers read:
        there a reading that finds nothing is a fault, not a gap."""
        return self.device["platform"] == "tpu"

    @property
    def peaks(self) -> Dict[str, float]:
        return cells.peaks(self.device["kind"])

    def per_layer_metrics(self) -> Dict[str, dict]:
        out = {}
        for m in self.cell.per_layer:
            value = cells.metric_reader(m["name"])(self)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    def host_activity(self, t: float) -> str:
        """The innermost service span open at monotonic time ``t``."""
        best = None
        for trace in self.spans:
            for s in trace["spans"]:
                if (s["duration_ms"] is not None
                        and s["start_s"] <= t < spans.end(s)
                        and (best is None or s["start_s"] > best["start_s"])):
                    best = s
        return best["name"] if best else "no service span"

    def breakdown(self) -> dict:
        if self.trace is None:
            return {"device_ops": [], "idle_gaps": []}
        ops = sorted(self.trace.ops.items(), key=lambda kv: -kv[1][1])
        return {"device_ops": [[name, secs] for name, (_, secs) in ops[:TOP]],
                "idle_gaps": [[self.host_activity(start), secs]
                              for start, secs in self.trace.gaps[:TOP]]}
