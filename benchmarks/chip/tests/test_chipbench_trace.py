"""The trace reduction, on a trace whose numbers are worked out by hand.

The planes mimic what the JAX profiler records on a TPU: a host plane with
the benchmark's sync marker, and a device plane whose "XLA Ops" line holds
the operations, each named by its HLO instruction, two of them
`svrg_update` calls. Times are nanoseconds on the trace's clock; the
marker sits at trace time 1,000 and host time 5,000, so the host clock is
the trace's plus 4,000. The last tests reduce a trace recorded on a v5e.
"""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import cells, readings



def _tpu_text(op, operands, target="tpu_custom_call", result="384,128"):
    """A custom call as a v5e's trace names its event: the whole HLO
    instruction, which names no kernel."""
    args = ", ".join(f"f32[{s}]{{1,0:T(8,128)S(1)}} %bitcast.{i}"
                     for i, s in enumerate(operands))
    return (f"%{op} = f32[{result}]{{1,0:T(8,128)S(1)}} "
            f"custom-call({args}), "
            f'custom_call_target="{target}", '
            "frontend_attributes={kernel_metadata={}}")


KERNEL_TEXT = _tpu_text("closed_call.12", ["1,1"] + ["384,128"] * 4)
# another Pallas kernel of the same result shape, which is not svrg_update
OTHER_TEXT = _tpu_text("closed_call.7", ["1,1"] + ["384,128"] * 3)
TPU_STATS = {"device_offset_ps": "114926326250",
             "device_duration_ps": "752500"}


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("chipbench.sync", 1_000, 10)])])
    ops = [
        _ev("fusion.1", 500, 700),        # starts before the window
        _ev("fusion.1", 1_200, 300),      # 1,200-1,500
        _ev(KERNEL_TEXT, 1_500, 200, **TPU_STATS),
        _ev("copy.2", 1_600, 400),        # overlaps the kernel: to 2,000
        _ev(KERNEL_TEXT, 3_000, 250, **TPU_STATS),
        _ev(OTHER_TEXT, 3_100, 100, **TPU_STATS),
        _ev("fusion.1", 9_000, 2_000),    # runs past the stop at 10,000
    ]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_group", 500, 10_500)]),
        NS(name=readings.OPS_LINE, events=ops)])
    other = NS(name="/device:TPU:0 SparseCore 0",
               lines=[NS(name=readings.OPS_LINE,
                         events=[_ev("fusion.9", 4_000, 4_000)])])
    return [host, device, other]


@pytest.fixture
def reduced():
    # sync marker at host 5,000 ns; the profiler stopped at host 14,000
    return readings.reduce_trace(_planes(), 5_000, 14_000, 1)


def test_window_is_the_traced_span(reduced):
    assert reduced.window_s == pytest.approx(9_000e-9)


def test_busy_is_the_union_of_operations_inside_the_window(reduced):
    # [1,000, 1,200) clipped from the first op, [1,200, 2,000) merged from
    # three overlapping ops, [3,000, 3,250), [9,000, 10,000) clipped: the
    # SparseCore plane and the module line are not operations of the chip
    assert reduced.busy_s == pytest.approx((200 + 800 + 250 + 1_000) * 1e-9)


def test_idle_gaps_are_on_the_host_clock_longest_first(reduced):
    # gaps [3,250, 9,000) and [2,000, 3,000), shifted by +4,000
    assert reduced.gaps == [(pytest.approx(7_250e-9), pytest.approx(5_750e-9)),
                            (pytest.approx(6_000e-9), pytest.approx(1_000e-9))]
    idle = sum(g for _, g in reduced.gaps)
    assert idle == pytest.approx(reduced.window_s - reduced.busy_s)


def test_kernel_calls_carry_their_time(reduced):
    # the second call of the other Pallas kernel does not count
    assert reduced.kernel_calls("svrg_update") == [pytest.approx(200e-9),
                                                   pytest.approx(250e-9)]


def test_operations_are_summed_by_name(reduced):
    assert reduced.ops["fusion.1"][0] == 3
    assert reduced.ops["fusion.1"][1] == pytest.approx((200 + 300 + 1_000)
                                                       * 1e-9)
    assert reduced.ops["copy.2"] == [1, pytest.approx(400e-9)]


def _kernel_us(calls, on_chip=False, traced=True):
    trace = readings.DeviceTrace(window_s=1.0, busy_s=0.5, ops={}, gaps=[],
                                 calls={"svrg_update": calls}, chips_seen=1)
    return cells.metric_reader("svrg_update_us")(
        NS(trace=trace if traced else None, on_chip=on_chip))


def test_kernel_time_per_call_by_hand():
    # three calls of 0.75, 0.75 and 0.78 us
    assert _kernel_us([0.75e-6, 0.75e-6, 0.78e-6]) == pytest.approx(0.76)


def test_a_trace_without_a_kernel_call_is_not_read_off_the_chip():
    assert _kernel_us([]) is None
    assert _kernel_us([1e-6], traced=False) is None


@pytest.mark.parametrize("traced", [True, False])
def test_on_a_tpu_a_missing_kernel_reading_is_an_error(traced):
    with pytest.raises(RuntimeError, match="svrg_update"):
        _kernel_us([], on_chip=True, traced=traced)


@pytest.mark.parametrize("chips_seen,busy_s", [(0, 0.0), (1, 0.0)])
def test_on_a_tpu_a_trace_without_device_work_is_an_error(chips_seen,
                                                          busy_s):
    trace = readings.DeviceTrace(window_s=1.0, busy_s=busy_s, ops={},
                                 gaps=[], calls={}, chips_seen=chips_seen)
    read = cells.metric_reader("device_idle")
    cell = NS(chips=1)
    assert read(NS(trace=trace, cell=cell, on_chip=False)) is None
    with pytest.raises(RuntimeError, match="device trace"):
        read(NS(trace=trace, cell=cell, on_chip=True))


def test_on_a_tpu_the_kernel_is_matched_by_its_operands():
    assert readings._is_svrg_update(KERNEL_TEXT)
    # a 2-row group: the rows lead every operand
    assert readings._is_svrg_update(_tpu_text(
        "closed_call.3", ["2,1,1"] + ["2,384,128"] * 4, result="2,384,128"))
    # a Mosaic call with other operands, a custom call that is no Mosaic
    # call, and an op that is no custom call are not svrg_update
    assert not readings._is_svrg_update(OTHER_TEXT)
    assert not readings._is_svrg_update(
        _tpu_text("closed_call.8", ["1,1"] + ["384,128"] * 4,
                  target="AllocateBuffer"))
    assert not readings._is_svrg_update(
        "%fusion.129 = f32[47236]{0:T(1024)S(1)} fusion(f32[1,10,47236]"
        "{2,1,0:T(8,128)S(1)} %get-tuple-element.695), kind=kCustom")


def test_on_a_tpu_operations_are_summed_by_their_short_name():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("chipbench.sync", 1_000, 10)])])
    device = NS(name="/device:TPU:0", lines=[NS(name=readings.OPS_LINE,
                                                events=[
        _ev(KERNEL_TEXT, 1_500, 200, **TPU_STATS),
        _ev(KERNEL_TEXT, 2_500, 300, **TPU_STATS)])])
    trace = readings.reduce_trace([host, device], 5_000, 14_000, 1)
    assert trace.ops == {"%closed_call.12": [2, pytest.approx(500e-9)]}
    assert trace.kernel_calls("svrg_update") == [pytest.approx(200e-9),
                                                 pytest.approx(300e-9)]


# A trace recorded on a v5e by record_trace.py: two runs of a 2-row
# AsySVRG group of 200 inner steps, 100 ms of host sleep between them.
RECORDED = Path(__file__).resolve().parent / "data" / "v5e_trace"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from jax.profiler import ProfileData
    meta = json.loads(RECORDED.with_suffix(".json").read_text())
    path = tmp_path_factory.mktemp("trace") / "v5e.xplane.pb"
    with gzip.open(RECORDED.with_suffix(".xplane.pb.gz")) as src:
        path.write_bytes(src.read())
    planes = list(ProfileData.from_file(str(path)).planes)
    return meta, planes, readings.reduce_trace(planes, meta["sync_ns"],
                                               meta["stop_ns"], 1)


def _device_ops(planes):
    """(start, end) of every operation on the chip's "XLA Ops" line, and
    the names of those ops."""
    plane = next(p for p in planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def test_recorded_trace_is_a_v5e_trace(recorded):
    meta, planes, trace = recorded
    assert meta["device_kind"] == "TPU v5 lite"
    assert trace.chips_seen == 1


def test_recorded_kernel_calls_are_one_per_inner_step(recorded):
    _, planes, trace = recorded
    # 2 runs x 10 threads x 20 inner steps, one call for the group's rows
    calls = trace.kernel_calls("svrg_update")
    assert len(calls) == 400
    by_hand = [(t - s) / 1e9 for s, t, name in _device_ops(planes)
               if 'custom_call_target="tpu_custom_call"' in name]
    assert sorted(calls) == pytest.approx(sorted(by_hand))


def test_recorded_busy_and_idle_by_hand(recorded):
    meta, planes, trace = recorded
    # the span from the sync marker to the stop, on the trace's clock
    marker = next(e for p in planes if p.name == "/host:CPU"
                  for line in p.lines for e in line.events
                  if e.name == readings.SYNC_MARKER)
    lo = int(marker.start_ns)
    hi = meta["stop_ns"] - (meta["sync_ns"] - lo)
    ops = sorted((max(s, lo), min(t, hi)) for s, t, _ in _device_ops(planes)
                 if min(t, hi) > max(s, lo))
    busy, end = 0, None
    for s, t in ops:
        if end is None or s > end:
            busy, end = busy + (t - s), t
        elif t > end:
            busy, end = busy + (t - end), t
    assert trace.window_s == pytest.approx((hi - lo) / 1e9)
    assert trace.busy_s == pytest.approx(busy / 1e9)
    assert trace.busy_s + sum(g for _, g in trace.gaps) == pytest.approx(
        trace.window_s)
    # the longest idle gap is the host's sleep between the two runs
    assert meta["sleep_s"] <= trace.gaps[0][1] < meta["sleep_s"] + 0.05
