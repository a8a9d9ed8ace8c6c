"""One place every Pallas kernel decides compiled / interpret / reference.

Before this module each ``kernels/*/ops.py`` carried its own ad-hoc
``_use_kernel()`` backend sniff plus ``interpret`` / ``force_kernel``
keyword plumbing, so the four kernels could (and did) drift in how they
picked an execution mode and tests had no uniform way to force one.
`kernel_mode` is now the single decision:

  * ``REPRO_KERNEL_MODE`` env var, when set, WINS — ``compiled`` /
    ``interpret`` / ``reference``. This is what the CI ``kernels-interpret``
    lane and local debugging use to force every kernel down one path. On a
    TPU backend ``interpret`` is refused: the interpreter would hide the
    device the run claims to use. ``reference`` stays allowed there, to
    measure a kernel against its jnp oracle on the chip.
  * Otherwise the caller's ``force_kernel`` / ``interpret`` flags and a
    backend sniff reproduce the historical per-kernel behaviour exactly:
    the Pallas body runs compiled on TPU (interpret-mode when asked),
    ``force_kernel=True`` opts non-TPU backends into the kernel body
    (tests pair it with ``interpret=True``), and everything else takes the
    jnp reference path.

Modes:
  ``compiled``  — ``pl.pallas_call(..., interpret=False)``: real Mosaic
                  lowering, the default on TPU. `chip_smoke.py` runs it on
                  the chip; tests/test_tpu_compile.py compiles it for a
                  described v5e topology.
  ``interpret`` — the kernel BODY executes under the Pallas interpreter
                  (plain XLA ops, any backend, bit-exact vs the same body
                  compiled only up to backend reduction order).
  ``reference`` — the kernel's jnp ``ref.py`` oracle (or, for the fused
                  sweep megakernel, the vmap engine) runs instead.
"""
from __future__ import annotations

import os

import jax

KERNEL_MODE_ENV = "REPRO_KERNEL_MODE"
_MODES = ("compiled", "interpret", "reference")


# Why the fused megakernel cannot run on a TPU today (found by compiling
# it for a described v5e topology; ROADMAP S6 holds the rewrite).
FUSED_TPU_BLOCKERS = (
    "the fused sweep-epoch megakernel does not compile for TPU: Mosaic "
    "refuses its (1, 2) PRNG-key row block, which is not aligned to the "
    "(8, 128) tiling, and every data array enters as one constant full "
    "block, which puts the whole dataset in VMEM. Use engine_mode='vmap' "
    "on TPU.")


def env_mode() -> str:
    """The ``REPRO_KERNEL_MODE`` override, validated; "" when unset.
    ``interpret`` on a TPU backend raises instead of running the Pallas
    interpreter where the chip is expected."""
    mode = os.environ.get(KERNEL_MODE_ENV, "").strip().lower()
    if mode and mode not in _MODES:
        raise ValueError(
            f"{KERNEL_MODE_ENV}={mode!r} — expected one of {_MODES}")
    if mode == "interpret" and kernel_backend() == "tpu":
        raise ValueError(
            f"{KERNEL_MODE_ENV}=interpret on a TPU backend would run the "
            "Pallas interpreter instead of the chip; unset it (compiled) or "
            "set 'reference' to measure the jnp oracle")
    return mode


def kernel_backend() -> str:
    """The backend the kernel dispatch sniffs (one place to monkeypatch)."""
    return jax.default_backend()


def kernel_mode(interpret: bool = False, force_kernel: bool = False) -> str:
    """'compiled' | 'interpret' | 'reference' for one kernel call.

    Env override first; else the historical contract shared by all
    kernels: the Pallas body runs iff ``force_kernel`` or the backend is
    TPU, in interpret mode iff ``interpret`` is set.
    """
    mode = env_mode()
    if mode:
        return mode
    if force_kernel or kernel_backend() == "tpu":
        return "interpret" if interpret else "compiled"
    return "reference"


def fused_sweep_mode() -> str:
    """'compiled' | 'interpret' for the fused sweep megakernel.

    On a TPU backend this raises `FUSED_TPU_BLOCKERS`: the kernel does not
    lower there, and substituting the interpreter or the vmap path would
    hide that. Elsewhere the megakernel has no separate jnp reference — the
    vmap engine IS its reference — so 'reference' is not a meaningful mode:
    it runs the interpreter (bit-exact to the vmap path), as does the
    default; a ``compiled`` override wins.
    """
    if kernel_backend() == "tpu":
        raise ValueError(FUSED_TPU_BLOCKERS)
    return "compiled" if env_mode() == "compiled" else "interpret"


def mode_tags(fused: bool) -> dict:
    """Span tags describing HOW a group dispatch executes — stamped onto
    the tracer's ``execute`` spans by `repro.core.sweep._dispatch_group`
    so a trace answers "which lowering ran this request" without anyone
    re-deriving the mode later (it can change with the environment). The
    resolution mirrors the runner-cache key (`_kernel_mode_key`): the
    megakernel mode for fused bodies, the `svrg_update` mode for vmap."""
    return {"engine_mode": "fused" if fused else "vmap",
            "backend": kernel_backend(),
            "kernel_mode": fused_sweep_mode() if fused else kernel_mode()}


def use_pallas(interpret: bool = False, force_kernel: bool = False) -> bool:
    """True when the Pallas kernel body should run (either mode)."""
    return kernel_mode(interpret, force_kernel) != "reference"


def pallas_interpret(interpret: bool = False,
                     force_kernel: bool = False) -> bool:
    """The ``interpret=`` flag to hand ``pl.pallas_call`` once the body
    runs. Only meaningful when `use_pallas` returned True."""
    return kernel_mode(interpret, force_kernel) == "interpret"
