"""The device trace read by the program's own names.

`chipbench.xplane` reads the ops' metadata that `ProfileData` leaves out;
`chipbench.scopes` reduces the ops by the scopes on their ``tf_op`` paths
and the program runs of the "XLA Modules" line. A plane worked out by
hand checks the reduction; the traces recorded on a v5e check the reader
(`record_trace.py`: a program before scopes) and the scopes
(`record_scoped_trace.py`).
"""
import gzip
import json
import statistics
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import cells, readings, scopes, xplane

DATA = Path(__file__).resolve().parent / "data"
STEP = "jit(g)/vmap()/while/body/closed_call/while/body/closed_call/inner_step"


# ------------------------------------------------------------ wire format
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _len(field, payload):
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field, value):
    return _varint(field << 3) + _varint(value)


def _str(field, text):
    return _len(field, text.encode())


def _entry(field, key, value):
    """One map entry: key field 1, value field 2."""
    return _len(field, _int(1, key) + _len(2, value))


def test_op_metadata_reads_stats_by_value_and_by_reference():
    stat_names = {1: "tf_op", 2: "source", 3: "program_id",
                  4: "kernels/svrg_update/kernel.py:39"}
    plane = _str(2, "/device:TPU:0")
    # lines are skipped whole: a line holding an event
    plane += _len(3, _str(2, "XLA Ops") + _len(4, _int(1, 7) + _int(2, 5)))
    plane += _entry(4, 7, _int(1, 7) + _str(2, "%svrg_update.3 = f32[64,128]")
                    + _len(5, _int(1, 1) + _str(5, STEP + "/pallas_call:"))
                    + _len(5, _int(1, 2) + _int(7, 4))
                    + _len(5, _int(1, 3) + _int(3, 2**63 + 5)))
    plane += _entry(4, 8, _int(1, 8) + _str(2, "%copy.1 = f32[2]"))
    for sid, name in stat_names.items():
        plane += _entry(5, sid, _int(1, sid) + _str(2, name))
    space = _len(1, plane) + _len(1, _str(2, "/host:CPU"))
    got = xplane.op_metadata(space)
    assert set(got) == {"/device:TPU:0", "/host:CPU"}
    ops = got["/device:TPU:0"]
    assert ops["%svrg_update.3 = f32[64,128]"] == xplane.OpMeta(
        tf_op=STEP + "/pallas_call:",
        source="kernels/svrg_update/kernel.py:39", program_id=2**63 + 5)
    assert ops["%copy.1 = f32[2]"] == xplane.OpMeta()


@pytest.mark.parametrize("tf_op,want", [
    ("jit(g)/vmap(loss)/while/body/add:", ({"loss": "jit(g)/vmap(loss)"},
                                           "add")),
    (STEP + "/svrg_update/svrg_update/pallas_call:",
     ({"inner_step": STEP, "svrg_update": STEP + "/svrg_update"},
      "pallas_call")),
    (STEP + "/read/read_unlock/jit(_uniform)/vmap()/while/body/add:",
     ({"inner_step": STEP, "read": STEP + "/read",
       "read_unlock": STEP + "/read/read_unlock"}, "add")),
    ("jit(traced)/vmap()/while/body/closed_call/vmap(jit(_threefry_split))"
     "/_epoch_core/xor:", ({}, "xor")),
])
def test_path_scopes_are_whole_components_at_their_place(tf_op, want):
    assert scopes.path_scopes(tf_op) == want


def test_scope_names_are_the_programs():
    from repro.core import asysvrg
    assert set(scopes.SCOPES) == set(asysvrg.SCOPES)
    assert (scopes.SNAPSHOT, scopes.INNER_STEP, scopes.READ, scopes.LOSS,
            scopes.SVRG_UPDATE) == (
        asysvrg.SNAPSHOT_SCOPE, asysvrg.INNER_STEP_SCOPE, asysvrg.READ_SCOPE,
        asysvrg.LOSS_SCOPE, "svrg_update")


# -------------------------------------------------- a plane worked by hand
LOSS0 = "jit(g)/vmap(loss)/select_n:"
LOSS0_BODY = "jit(g)/vmap(loss)/while/body/add:"
LOSS = "jit(g)/vmap()/while/body/closed_call/loss/while:"
SNAP = "jit(g)/vmap()/while/body/closed_call/snapshot/reduce_sum:"
READ = STEP + "/read/read_unlock/gather:"
KERNEL = STEP + "/svrg_update/svrg_update/pallas_call:"
WRITE = STEP + "/scatter:"
LOOP = "jit(g)/vmap()/while/body/closed_call/while/body/dynamic_slice:"
TINY = "jit(convert_element_type)/convert_element_type:"

# (name, tf_op, program) of each op; an op's name is its HLO text. A
# loop's while op carries no tf_op.
META = {"%while.1": ("", 1), "%add.1": (LOSS0_BODY, 1),
        "%snap": (SNAP, 1), "%read": (READ, 1), "%svrg_update.1": (KERNEL, 1),
        "%write": (WRITE, 1), "%slice": (LOOP, 1), "%copy": ("", 1),
        "%while.2": (LOSS, 1), "%convert": (TINY, 2), "%mul": (LOSS0, 1)}
OPS = [  # start, duration on the trace's clock (ns)
    ("%while.1", 1_000, 400), ("%add.1", 1_050, 100), ("%add.1", 1_200, 100),
    ("%snap", 1_400, 200),
    ("%read", 1_600, 300), ("%svrg_update.1", 1_900, 50),
    ("%write", 1_950, 50), ("%slice", 2_000, 10),
    ("%read", 2_010, 300), ("%svrg_update.1", 2_310, 50),
    ("%write", 2_360, 50), ("%copy", 2_500, 100), ("%while.2", 3_000, 900),
    ("%convert", 4_500, 100),
    # a run of program 1 still going when the profiler stops
    ("%mul", 5_000, 200), ("%read", 5_300, 300),
    ("%svrg_update.1", 5_600, 50), ("%read", 10_900, 600),
]


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def _hand_planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev(readings.SYNC_MARKER, 1_000, 10)])])
    device = NS(name="/device:TPU:0", lines=[
        NS(name=scopes.MODULES_LINE, events=[
            _ev("jit_g(1)", 900, 3_100), _ev("jit_convert(2)", 4_500, 100),
            _ev("jit_g(1)", 5_000, 7_000)]),
        NS(name=readings.OPS_LINE, events=[_ev(*op) for op in OPS])])
    meta = {"/device:TPU:0": {name: xplane.OpMeta(tf_op=tf_op, program_id=pid)
                              for name, (tf_op, pid) in META.items()}}
    return [host, device], meta


@pytest.fixture
def hand():
    planes, meta = _hand_planes()
    return scopes.reduce_scopes(planes, meta, 1_000, 11_000, 1)


def test_scope_seconds_count_each_ops_own_time(hand):
    # the loss at w0's while op (no tf_op) spans its two body ops: its
    # own 400 - 200 counts for the scope they share, + 200 of body, + 900
    # after the epoch, + 200 in the next run
    assert hand.scope_s["loss"] == pytest.approx(1_500e-9)
    assert hand.scope_s["snapshot"] == pytest.approx(200e-9)
    # three whole steps of 300 + 50 (+ 50 written after the kernel, in
    # two of them) and 100 ns of a read the window's end cuts
    assert hand.scope_s["inner_step"] == pytest.approx(1_250e-9)
    assert hand.scope_s["read"] == pytest.approx(1_000e-9)
    assert hand.scope_s["read_unlock"] == hand.scope_s["read"]
    assert hand.scope_s["svrg_update"] == pytest.approx(150e-9)
    # own times add up to the busy union: nothing counted twice
    assert hand.busy_s == pytest.approx(3_160e-9)
    unscoped = 10e-9 + 100e-9 + 100e-9          # %slice, %copy, %convert
    assert (hand.scope_s["loss"] + hand.scope_s["snapshot"]
            + hand.scope_s["inner_step"] + unscoped) == pytest.approx(
        hand.busy_s)


def test_calls_are_places_in_segments_between_kernel_calls_and_runs(hand):
    assert hand.inner_steps == 3
    # the loss at w0, after the epoch, and at w0 of the next run
    assert hand.scope_calls["loss"] == 3
    assert hand.scope_calls["snapshot"] == 1
    assert hand.scope_calls["svrg_update"] == 3
    # one read per step, and the one the window's end cuts
    assert hand.scope_calls["read"] == 4
    assert hand.named_ops == len(OPS) - 2     # %while.1, %copy: no tf_op


def test_program_runs_are_the_module_events_in_the_window(hand):
    # [1,000, 4,000), [4,500, 4,600) and [5,000, 11,000): the first and
    # the last cut at the window's ends
    assert hand.window_s == pytest.approx(10_000e-9)
    assert hand.program_s == pytest.approx(9_100e-9)
    assert hand.chips_seen == 1


def _reader(name, scope_trace, on_chip=False, chips=1):
    r = NS(scopes=scope_trace, trace=None, on_chip=on_chip,
           cell=NS(chips=chips))
    return cells.metric_reader(name)(r)


def test_scope_metrics_by_hand(hand):
    assert _reader("inner_step_us", hand) == pytest.approx(1.25 / 3)
    assert _reader("read_us", hand) == pytest.approx(1.0 / 3)
    assert _reader("snapshot_ms", hand) == pytest.approx(2e-4)
    assert _reader("loss_ms", hand) == pytest.approx(5e-4)
    assert _reader("idle_between_programs", hand) == pytest.approx(9.0)


SCOPE_METRICS = {"inner_step_us": "inner_step", "read_us": "read",
                 "snapshot_ms": "snapshot", "loss_ms": "loss"}
DEVICE_METRICS = sorted({*SCOPE_METRICS, "idle_between_programs", *(
    m["name"] for m in cells.load_benchmark()["per_layer"]
    if m["source"] == "device_trace")})


@pytest.mark.parametrize("metric", DEVICE_METRICS)
def test_device_metrics_read_nothing_off_the_chip_without_a_trace(metric):
    assert _reader(metric, None) is None
    with pytest.raises(RuntimeError, match="device trace"):
        _reader(metric, None, on_chip=True)


@pytest.mark.parametrize("metric", SCOPE_METRICS)
def test_on_a_tpu_a_trace_that_lacks_the_scope_is_an_error(hand, metric):
    lacking = scopes.ScopeTrace(
        **{**vars(hand),
           "scope_s": {k: v for k, v in hand.scope_s.items()
                       if k != SCOPE_METRICS[metric]},
           "scope_calls": {k: v for k, v in hand.scope_calls.items()
                           if k != SCOPE_METRICS[metric]}})
    assert _reader(metric, lacking) is None
    with pytest.raises(RuntimeError, match=SCOPE_METRICS[metric]):
        _reader(metric, lacking, on_chip=True)


@pytest.mark.parametrize("metric", SCOPE_METRICS)
def test_a_program_that_names_no_scope_reads_nothing_on_a_tpu(metric):
    """The benchmark also runs over a program that predates the scopes:
    its ops carry ``tf_op`` paths without any of the names."""
    planes, meta = _hand_planes()
    for name, m in meta["/device:TPU:0"].items():
        meta["/device:TPU:0"][name] = xplane.OpMeta(
            tf_op=m.tf_op and "jit(g)/while/body/add:")
    unscoped = scopes.reduce_scopes(planes, meta, 1_000, 11_000, 1)
    assert unscoped.scope_s == {} and unscoped.named_ops > 0
    assert _reader(metric, unscoped, on_chip=True) is None
    assert _reader("idle_between_programs", unscoped,
                   on_chip=True) == pytest.approx(9.0)


def test_idle_between_programs_needs_the_cells_chips(hand):
    assert _reader("idle_between_programs", hand, chips=4) is None
    with pytest.raises(RuntimeError, match="1 of 4"):
        _reader("idle_between_programs", hand, on_chip=True, chips=4)


def _span(name, start_s, ms):
    return {"name": name, "start_s": start_s, "duration_ms": ms}


def test_encode_ms_is_the_median_encode_span():
    traces = [{"spans": [_span("submit", 0.0, 1.0),
                         _span("encode", 2.0, ms)]} for ms in (3.0, 1.0, 8.0)]
    traces.append({"spans": [_span("submit", 0.0, 1.0),
                             _span("encode", 5.0, None)]})   # still open
    read = cells.metric_reader("encode_ms")
    assert read(NS(spans=traces)) == statistics.median([3.0, 1.0, 8.0])
    assert read(NS(spans=[{"spans": [_span("submit", 0.0, 1.0)]}])) is None


# ------------------------------------------------- traces recorded on a v5e
def _recorded(name):
    from jax.profiler import ProfileData
    meta = json.loads((DATA / f"{name}.json").read_text())
    data = gzip.decompress((DATA / f"{name}.xplane.pb.gz").read_bytes())
    planes = list(ProfileData.from_serialized_xspace(data).planes)
    marker = next(e for p in planes if p.name == "/host:CPU"
                  for line in p.lines for e in line.events
                  if e.name == readings.SYNC_MARKER)
    lo = int(marker.start_ns)
    hi = meta["stop_ns"] - (meta["sync_ns"] - lo)
    ops = xplane.op_metadata(data)
    return (meta, planes, ops,
            readings.reduce_trace(planes, meta["sync_ns"], meta["stop_ns"], 1),
            scopes.reduce_scopes(planes, ops, lo, hi, 1))


@pytest.fixture(scope="module")
def before_scopes():
    return _recorded("v5e_trace")


@pytest.fixture(scope="module")
def scoped():
    return _recorded("v5e_scoped_trace")


def _kernel_ops(planes, ops):
    plane = next(p for p in planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == readings.OPS_LINE)
    return [(e.name, ops[plane.name][e.name]) for e in line.events
            if readings._is_svrg_update(e.name)]


def test_recorded_kernel_calls_carry_their_name_stack_and_source(
        before_scopes):
    _, planes, ops, trace, _ = before_scopes
    calls = _kernel_ops(planes, ops)
    assert len(calls) == 400 == len(trace.kernel_calls("svrg_update"))
    for _, meta in calls:
        assert meta.tf_op.endswith("/pallas_call:")
        assert "kernels/svrg_update/kernel.py:" in meta.source
        assert meta.program_id is not None


def test_recorded_sleep_lies_between_programs(before_scopes):
    meta, _, _, trace, st = before_scopes
    assert st.window_s == pytest.approx(trace.window_s)
    assert st.busy_s == pytest.approx(trace.busy_s)
    # the 100 ms host sleep: outside every program run
    idle_between = st.window_s - st.program_s
    assert meta["sleep_s"] <= idle_between < meta["sleep_s"] + 0.05
    # the device's idle time inside program runs: under 1 ms
    in_program = (st.window_s - st.busy_s) - idle_between
    assert 0 <= in_program < 1e-3
    # a program that names no scope: nothing to read, nothing raised
    assert st.scope_s == {} and st.named_ops > 0
    assert _reader("read_us", st, on_chip=True) is None


def test_scoped_kernel_calls_are_the_inner_steps(scoped):
    _, planes, ops, trace, st = scoped
    # 2 runs x 10 threads x 20 inner steps, one call for the group's rows
    assert st.inner_steps == 400 == len(trace.kernel_calls("svrg_update"))
    for _, meta in _kernel_ops(planes, ops):
        found, op = scopes.path_scopes(meta.tf_op)
        assert list(found) == ["inner_step", "svrg_update"]
        assert op == "pallas_call"
    # each run: the loss at w0 and after its epoch (which this small
    # program's schedule interleaves at its end), one snapshot
    assert st.scope_calls["loss"] == 4
    assert st.scope_calls["snapshot"] == 2


def test_scoped_trace_scopes_cover_the_busy_time(scoped):
    _, _, _, _, st = scoped
    top = sum(st.scope_s[s] for s in ("snapshot", "inner_step", "loss"))
    assert 0.95 * st.busy_s <= top <= st.busy_s * (1 + 1e-9)


def test_scoped_trace_reads_lie_in_the_step(scoped):
    _, _, ops, _, st = scoped
    assert 0 < st.scope_s["read"] < st.scope_s["inner_step"]
    for meta in ops["/device:TPU:0"].values():
        found, _ = scopes.path_scopes(meta.tf_op)
        if "read" in found or "svrg_update" in found:
            assert "inner_step" in found, meta.tf_op
