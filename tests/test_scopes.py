"""Named scopes on the epoch cores' device work.

`jax.named_scope` writes a scope's name into the metadata of every op
traced inside it, which a device trace reports as the op's ``tf_op`` path,
and nowhere else. The group runners' HLO carries every scope of
`repro.core.asysvrg.SCOPES`, and a runner traced with the scopes turned
off compiles to the same program, op for op, and gives the same bits.
"""
import contextlib
import re

import jax
import numpy as np
import pytest

from repro.core import LogisticRegression, SweepSpec
from repro.core import asysvrg
from repro.core.sweep import _group_fn, _row_args, plan_sweep
from repro.data.libsvm import make_synthetic_libsvm
from repro.kernels.svrg_update import ops as svrg_update_ops

SCHEMES = ("consistent", "inconsistent", "unlock")
# every scope but the ones only the AsySVRG update has
HOGWILD_SCOPES = set(asysvrg.SCOPES) - {asysvrg.SNAPSHOT_SCOPE,
                                        svrg_update_ops.SCOPE}


@pytest.fixture(scope="module")
def obj():
    ds = make_synthetic_libsvm("rcv1", seed=0, scale=0.004)
    return LogisticRegression(ds.X, ds.y, 1e-4)


def _runner(obj, algo):
    """The group function and its arguments for one group of three unlock
    rows over 2 epochs. Every scheme's rows run this one program, which
    holds each reader branch and the drop mask."""
    specs = [SweepSpec(algo=algo, scheme="unlock", step_size=0.5, tau=3,
                       num_threads=4, seed=k) for k in range(3)]
    plan = plan_sweep(obj, 2, specs)
    (key, members), = plan.groups.items()
    engine = key.engine
    fn, _ = _group_fn(engine, obj=obj, num_data=len(obj.data_args()),
                      epochs=2, total=key.total, buf_len=key.buf_len,
                      option=key.option, drop_prob=0.02)
    args = _row_args(engine, plan.specs, plan.resolved, members,
                     obj.init_flat())
    return fn, (*obj.data_args(), *args)


def _op_names(text: str):
    """The name stacks in a program's text: ``op_name`` metadata in
    compiled HLO, the named ``loc("...")`` locations in the lowered module
    (relative inside a loop's body; its other locations name files)."""
    return {a or b for a, b in re.findall(
        r'op_name="([^"]*)"|loc\("([^"<]*)"', text)
        if ".py" not in (a or b)}


def _scopes_in(op_names):
    found = set()
    for name in op_names:
        for part in name.split("/"):
            # a scope opened right inside a transform prints as vmap(loss)
            while (m := re.match(r"^[\w.]+\((.*)\)$", part)):
                part = m.group(1)
            found.add(part)
    return found & set(asysvrg.SCOPES)


def test_scope_names_are_distinct_and_name_each_reader():
    assert len(set(asysvrg.SCOPES)) == len(asysvrg.SCOPES)
    assert asysvrg.READER_SCOPES == tuple(f"read_{s}" for s in SCHEMES)
    assert svrg_update_ops.SCOPE in asysvrg.SCOPES


@pytest.mark.parametrize("algo,want", [("asysvrg", set(asysvrg.SCOPES)),
                                       ("hogwild", HOGWILD_SCOPES)])
def test_lowered_group_runner_names_every_scope(obj, algo, want):
    fn, args = _runner(obj, algo)
    hlo = jax.jit(fn).lower(*args).as_text(debug_info=True)
    assert _scopes_in(_op_names(hlo)) == want


def test_update_kernel_ops_sit_in_the_step(obj):
    """The update and the reads are inside the inner step, never outside
    it; each reader branch is inside `read`."""
    fn, args = _runner(obj, "asysvrg")
    names = _op_names(jax.jit(fn).lower(*args).compile().as_text())
    for name in names:
        parts = name.split("/")
        if svrg_update_ops.SCOPE in parts or asysvrg.READ_SCOPE in parts:
            assert asysvrg.INNER_STEP_SCOPE in parts, name
        for reader in asysvrg.READER_SCOPES:
            if reader in parts:
                assert (parts.index(asysvrg.READ_SCOPE)
                        < parts.index(reader)), name


def _without_debug_info(hlo: str) -> str:
    """Compiled HLO text less what names where an op came from: op
    metadata and the file, function and stack tables before the
    computations."""
    hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    return "\n\n".join(
        block for block in hlo.split("\n\n")
        if not re.match(r"^(FileNames|FunctionNames|FileLocations|"
                        r"StackFrames)\n", block))


@pytest.mark.parametrize("algo", ["asysvrg", "hogwild"])
def test_scopes_change_no_op_and_no_bit(obj, algo, monkeypatch):
    fn, args = _runner(obj, algo)
    scoped = jax.jit(fn).lower(*args).compile()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    fn, args = _runner(obj, algo)
    plain = jax.jit(fn).lower(*args).compile()
    assert _scopes_in(_op_names(plain.as_text())) == set()
    assert (_without_debug_info(scoped.as_text())
            == _without_debug_info(plain.as_text()))
    for a, b in zip(scoped(*args), plain(*args)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
