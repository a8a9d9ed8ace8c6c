"""repro-lint (repro.analysis) — per-rule fixtures and tree-level gates.

Each rule gets a known-bad fixture (must be diagnosed, with the right
code, on the right line) and a known-good twin (must stay silent): the
linter's job is to catch the seeded violation AND not cry wolf on the
sanctioned pattern. The capstone test pins the shipped tree clean — the
same invocation the CI repro-lint lane runs.

The linter is stdlib-only, so nothing here imports jax.
"""
import configparser
import re
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import RULES, lint_paths, lint_source

REPO = Path(__file__).resolve().parent.parent


def codes(diags):
    return [d.code for d in diags]


# --------------------------------------------------------------------- RL001
BAD_RL001_AXISLESS = """\
import jax.numpy as jnp

def sample_grad_stable(x, w):
    return jnp.sum(x * w)
"""

BAD_RL001_MATMUL = """\
import jax.numpy as jnp

def loss_fixed_order(X, w):
    margins = X @ w
    return jnp.dot(margins, margins)
"""

GOOD_RL001 = """\
import jax.numpy as jnp

def sample_grad_stable(x, w):
    return jnp.sum(x * w, axis=-1)

def loss_fixed_order(X, w):
    return _fixed_order_sum(X * w[None, :])

def unstable_helper(X, w):
    return X @ w  # out of scope: not a *_stable / loss_fixed_order name
"""


def test_rl001_flags_axisless_reduce():
    diags = lint_source(BAD_RL001_AXISLESS)
    assert codes(diags) == ["RL001"]
    assert diags[0].line == 4
    assert "axis-less `jnp.sum`" in diags[0].message


def test_rl001_flags_matmul_and_dot():
    diags = lint_source(BAD_RL001_MATMUL)
    assert codes(diags) == ["RL001", "RL001"]
    assert [d.line for d in diags] == [4, 5]


def test_rl001_good_patterns_clean():
    assert lint_source(GOOD_RL001) == []


# --------------------------------------------------------------------- RL002
BAD_RL002_CAPTURE = """\
import jax
import jax.numpy as jnp

def driver(obj, w):
    data = obj.data_args()
    loss_fn = jax.jit(lambda w_: obj.flat_loss(data, w_))
    return loss_fn(w)
"""

BAD_RL002_TRACER_IF = """\
def _epoch_core(w, eta, *, drop_prob):
    if eta > 0:
        w = w * eta
    return w
"""

BAD_RL002_UNHASHABLE = """\
class Obj:
    def runner_static_key(self):
        return [self.n, self.p]
"""

GOOD_RL002 = """\
import jax
import jax.numpy as jnp

def driver(obj, w):
    data = obj.data_args()
    loss_fn = jax.jit(lambda d, w_: obj.flat_loss(d, w_))
    return loss_fn(data, w)

def _epoch_core(w, eta, *, drop_prob):
    if drop_prob > 0:          # kw-only param: static by convention
        w = w * eta
    if w.ndim == 2:            # shape probe: static under tracing
        w = w[0]
    return w

class Obj:
    def runner_static_key(self):
        return (self.n, tuple(sorted(self.names)))
"""


def test_rl002_flags_array_closure_capture():
    diags = lint_source(BAD_RL002_CAPTURE)
    assert codes(diags) == ["RL002"]
    assert diags[0].line == 6
    assert "closes over array-valued 'data'" in diags[0].message


def test_rl002_flags_python_if_on_tracer():
    diags = lint_source(BAD_RL002_TRACER_IF)
    assert codes(diags) == ["RL002"]
    assert diags[0].line == 2
    assert "'eta'" in diags[0].message


def test_rl002_flags_unhashable_static_key():
    diags = lint_source(BAD_RL002_UNHASHABLE)
    assert codes(diags) == ["RL002"]
    assert "unhashable" in diags[0].message


def test_rl002_good_patterns_clean():
    assert lint_source(GOOD_RL002) == []


# --------------------------------------------------------------------- RL003
BAD_RL003 = """\
import threading

class Daemon:
    def __init__(self):
        self._lock = threading.Lock()
        self.stats = 0  # guarded-by: _lock

    def bump(self):
        self.stats += 1
"""

GOOD_RL003 = """\
import threading

class Daemon:
    def __init__(self):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self.stats = 0  # guarded-by: _lock

    def bump(self):
        with self._lock:
            self.stats += 1

    def bump_via_cv(self):
        with self._cv:             # Condition(self._lock) aliases _lock
            self.stats += 1

    def _bump_locked(self):  # holds: _lock
        self.stats += 1
"""

BAD_RL003_ESCAPED_CLOSURE = """\
import threading

class Daemon:
    def __init__(self):
        self._lock = threading.Lock()
        self.stats = 0  # guarded-by: _lock

    def make_bumper(self):
        with self._lock:
            def bump():            # closure outlives the with-block
                self.stats += 1
            return bump
"""


def test_rl003_flags_unlocked_access():
    diags = lint_source(BAD_RL003)
    assert codes(diags) == ["RL003"]
    assert diags[0].line == 9
    assert "`self.stats` is guarded by `_lock`" in diags[0].message


def test_rl003_lock_condition_alias_and_holds_clean():
    assert lint_source(GOOD_RL003) == []


def test_rl003_nested_closure_does_not_inherit_lock():
    diags = lint_source(BAD_RL003_ESCAPED_CLOSURE)
    assert codes(diags) == ["RL003"]
    assert diags[0].line == 11


# --------------------------------------------------------------------- RL004
BAD_RL004_SWEEP = """\
from typing import NamedTuple

class _Resolved(NamedTuple):
    engine: str
    buf_len: int
    tau: int

def plan_sweep(resolved):
    groups = {}
    for c, r in enumerate(resolved):
        groups.setdefault((r.engine,), []).append(c)
    return groups

def _dispatch_group(resolved, members):
    return [resolved[c].tau for c in members]
"""

GOOD_RL004_SWEEP = """\
from typing import NamedTuple

class _Resolved(NamedTuple):
    engine: str
    buf_len: int
    tau: int

def plan_sweep(resolved):
    groups = {}
    for c, r in enumerate(resolved):
        groups.setdefault((r.engine, r.buf_len), []).append(c)
    return groups

def _dispatch_group(resolved, members):
    return [resolved[c].tau for c in members]
"""

BAD_RL004_CACHE = """\
def runner_key(engine, *, total, buf_len):
    return (engine, total)

def get_group_runner(engine, *, total, buf_len):
    key = runner_key(engine, total=total, buf_len=buf_len)
    return key
"""


def test_rl004_flags_unkeyed_resolved_field():
    diags = lint_source(BAD_RL004_SWEEP)
    assert codes(diags) == ["RL004"]
    assert diags[0].line == 5              # the buf_len field declaration
    assert "_Resolved.buf_len" in diags[0].message


def test_rl004_keyed_field_clean():
    assert lint_source(GOOD_RL004_SWEEP) == []


@pytest.mark.parametrize("key, bad", [
    ("_Key(r.engine)", True),
    ("_Key(r.engine, buf_len=r.buf_len)", False),
    ("_Key(r.engine, r.buf_len)", False),
])
def test_rl004_reads_a_named_key_constructor(key, bad):
    """A group key built by a key type's constructor (a NamedTuple) is
    read like a tuple literal: its positional and keyword fields."""
    src = GOOD_RL004_SWEEP.replace("(r.engine, r.buf_len)", key)
    diags = lint_source(src)
    assert codes(diags) == (["RL004"] if bad else [])
    if bad:
        assert "_Resolved.buf_len" in diags[0].message


def test_rl004_flags_key_param_never_read():
    diags = lint_source(BAD_RL004_CACHE)
    assert codes(diags) == ["RL004"]
    assert "'buf_len'" in diags[0].message


# --------------------------------------------------------------------- RL005
KERNEL_IMPURE = """\
import os

def sweep_epoch_kernel(w_ref, o_ref):
    print("tracing")
    mode = os.environ.get("REPRO_KERNEL_MODE")
    o_ref[...] = w_ref[...]
"""


def test_rl005_flags_impurity_in_kernel_module_only():
    diags = lint_source(KERNEL_IMPURE,
                        path="src/repro/kernels/sweep/kernel.py")
    assert codes(diags) == ["RL005", "RL005"]
    assert [d.line for d in diags] == [4, 5]
    # identical code outside kernels/**/kernel.py is out of scope
    assert lint_source(KERNEL_IMPURE, path="src/repro/core/helper.py") == []


# --------------------------------------------------------------------- RL006
BAD_RL006_CORE = """\
import time

def epoch_core(w, key):
    t0 = time.perf_counter()
    tr = tracer()
    tr.annotate(started=t0)
    return w
"""

BAD_RL006_KERNEL = """\
import time

def sweep_body(w_ref, o_ref):
    t0 = time.monotonic_ns()
    hist.observe(t0)
    o_ref[...] = w_ref[...]
"""

GOOD_RL006_BRACKETS = """\
import time

def dispatch_group(runner, args):
    t0 = time.perf_counter()
    with tracer().span_active("execute"):
        out = runner(*args)
    hist.observe(time.perf_counter() - t0)
    return out
"""


def test_rl006_flags_obs_calls_inside_core_scopes():
    diags = lint_source(BAD_RL006_CORE)
    assert codes(diags) == ["RL006", "RL006", "RL006"]
    assert [d.line for d in diags] == [4, 5, 6]
    assert "bracket the compiled program" in diags[0].message


def test_rl006_flags_kernel_modules_wholesale():
    diags = lint_source(BAD_RL006_KERNEL,
                        path="src/repro/kernels/sweep/kernel.py")
    assert codes(diags) == ["RL006", "RL006"]
    # the same code outside kernels/**/kernel.py and outside *_core scopes
    # is exactly where obs calls belong
    assert lint_source(BAD_RL006_KERNEL,
                       path="src/repro/core/helper.py") == []


def test_rl006_allows_observability_at_the_dispatch_site():
    assert lint_source(GOOD_RL006_BRACKETS) == []


BAD_RL006_LIVE_OBS = """\
def sweep_core(w, hist):
    bus = progress_bus()
    bus.publish(kind="slice")
    enforce_group(wd, hist, w)
    with jax.named_scope("inner_step"):
        w = w + 1
    return w
"""


def test_rl006_flags_progress_watchdog_ledger_inside_core_scopes():
    """The live-progress bus and the divergence watchdog are host-side by
    contract — any call inside a jitted scope is flagged, same as the
    tracer API. `jax.named_scope` only names the ops in their metadata,
    so it is allowed there (the performance ledger is gone, and its
    names are no findings any more)."""
    diags = lint_source(BAD_RL006_LIVE_OBS)
    assert codes(diags) == ["RL006"] * 3
    assert [d.line for d in diags] == [2, 3, 4]
    assert any("progress-bus" in d.message for d in diags)
    assert any("watchdog" in d.message for d in diags)
    assert lint_source("def sweep_core(w):\n    led = ledger()\n"
                       "    return w\n") == []
    # the identical calls outside *_core scopes are exactly where they
    # belong (dispatch sites, services, HTTP handlers)
    assert lint_source(BAD_RL006_LIVE_OBS.replace(
        "sweep_core", "dispatch_site")) == []


# --------------------------------------------------------- suppression (RL000)
def test_suppression_with_reason_silences_finding():
    src = BAD_RL001_AXISLESS.replace(
        "return jnp.sum(x * w)",
        "return jnp.sum(x * w)  # repro-lint: ignore[RL001] x,w are 1-D here")
    assert lint_source(src) == []


def test_reasonless_suppression_is_reported():
    src = BAD_RL001_AXISLESS.replace(
        "return jnp.sum(x * w)",
        "return jnp.sum(x * w)  # repro-lint: ignore[RL001]")
    diags = lint_source(src)
    assert codes(diags) == ["RL000"]
    assert "no reason" in diags[0].message


def test_stale_suppression_is_reported():
    src = GOOD_RL001 + "\nX = 1  # repro-lint: ignore[RL001] nothing here\n"
    diags = lint_source(src)
    assert codes(diags) == ["RL000"]
    assert "unused suppression" in diags[0].message


def test_unknown_code_suppression_is_reported():
    src = "X = 1  # repro-lint: ignore[RL999] bogus code\n"
    diags = lint_source(src)
    assert codes(diags) == ["RL000"]
    assert "unknown rule code" in diags[0].message


def test_select_subsetting_skips_stale_check():
    src = BAD_RL002_TRACER_IF + "\nY = 1  # repro-lint: ignore[RL001] kept\n"
    diags = lint_source(src, select={"RL001"})
    assert diags == []                     # RL002 unselected, RL001 not stale
    assert codes(lint_source(src, select={"RL002"})) == ["RL002"]


def test_hash_inside_string_is_not_a_suppression():
    src = ('MSG = "use # repro-lint: ignore[RL001] sparingly"\n')
    assert lint_source(src) == []


# ------------------------------------------------------------- tree + CLI
def test_shipped_tree_is_clean():
    result = lint_paths([str(REPO / "src"), str(REPO / "tests"),
                         str(REPO / "benchmarks")])
    assert result.diagnostics == [], "\n".join(
        d.render() for d in result.diagnostics)
    assert len(result.files) > 100        # the walk actually found the tree


def test_cli_exits_zero_on_src(tmp_path):
    out = tmp_path / "BENCH_repro_lint.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "src", "tests",
         "benchmarks", "--json-out", str(out)],
        cwd=REPO, capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json
    payload = json.loads(out.read_text())
    assert payload["diagnostics"] == []
    assert payload["files"] > 100
    assert set(payload["rules"]) == set(RULES)


def test_cli_nonzero_on_seeded_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(BAD_RL001_AXISLESS)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(bad)],
        cwd=REPO, capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 1
    assert "RL001" in proc.stdout


def test_cli_rejects_unknown_select():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--select", "RL042", "src"],
        cwd=REPO, capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 2
    assert "unknown rule code" in proc.stderr


# ------------------------------------------------------------- meta checks
_BUILTIN_MARKS = {"parametrize", "skip", "skipif", "xfail", "usefixtures",
                  "filterwarnings"}


def test_all_markers_registered():
    """Every pytest.mark.<name> used under tests/ is declared in pytest.ini
    (unregistered marks are typo-silent without --strict-markers)."""
    ini = configparser.ConfigParser()
    ini.read(REPO / "pytest.ini")
    registered = {line.split(":")[0].strip()
                  for line in ini["pytest"]["markers"].strip().splitlines()}
    used = set()
    for path in (REPO / "tests").glob("test_*.py"):
        used |= set(re.findall(r"pytest\.mark\.(\w+)", path.read_text()))
    unregistered = used - _BUILTIN_MARKS - registered
    assert not unregistered, (
        f"marks used but not registered in pytest.ini: {unregistered}")
