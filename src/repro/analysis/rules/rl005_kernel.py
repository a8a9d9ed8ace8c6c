"""RL005 — Pallas kernel bodies are pure.

``src/repro/kernels/*/kernel.py`` holds the Pallas kernel bodies and
their `pallas_call` builders. Those modules are traced inside jit;
anything effectful there is either silently dropped by tracing (prints),
breaks interpret/compiled parity (host callbacks), or makes the compiled
program depend on ambient process state that the cache key cannot see
(environment sniffing — the exact hazard `runner_key`'s kernel-mode
facet exists to prevent: mode decisions belong in
`repro.kernels.dispatch`, resolved at KEY time, never inside a kernel
module).

Flagged anywhere in a ``kernels/**/kernel.py`` file:

  * ``print(...)`` / ``breakpoint()`` — debugging leftovers; use
    ``pl.debug_print`` behind interpret mode, outside the shipped body;
  * host-callback escapes: ``jax.debug.print``, ``jax.debug.callback``,
    ``io_callback``, ``pure_callback``, ``host_callback.*``;
  * environment sniffing: ``os.environ``, ``os.getenv``,
    ``os.environ.get`` — route through ``kernels/dispatch``;
  * file I/O: ``open(...)``.
"""
from __future__ import annotations

import ast
from pathlib import PurePath
from typing import List

from repro.analysis.astutil import call_name, dotted_name
from repro.analysis.diagnostics import Diagnostic

_BANNED_CALLS = {
    "print": "stray print is dropped by tracing (or spams per trace)",
    "breakpoint": "debugger hook in a kernel module",
    "open": "file I/O in a kernel module",
    "jax.debug.print": "host callback breaks interpret/compiled parity",
    "jax.debug.callback": "host callback breaks interpret/compiled parity",
    "jax.experimental.io_callback": "host callback in a kernel body",
    "io_callback": "host callback in a kernel body",
    "jax.pure_callback": "host callback in a kernel body",
    "pure_callback": "host callback in a kernel body",
    "os.getenv": "env sniffing — mode decisions live in kernels/dispatch "
                 "so the cache key sees them",
}
# os.environ covers os.environ.get/[...] via the attribute check
_BANNED_NAMES = {
    "os.environ": "env sniffing — mode decisions live in kernels/dispatch "
                  "so the cache key sees them",
}


def _in_scope(path: str) -> bool:
    p = PurePath(path)
    return p.name == "kernel.py" and "kernels" in p.parts


def check(path: str, tree: ast.AST, source: str) -> List[Diagnostic]:
    if not _in_scope(path):
        return []
    out: List[Diagnostic] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = call_name(node)
            why = _BANNED_CALLS.get(name or "")
            if why is not None:
                out.append(Diagnostic(
                    path, node.lineno, "RL005",
                    f"impure `{name}(...)` in a Pallas kernel module — "
                    f"{why}"))
        elif isinstance(node, ast.Attribute):
            name = dotted_name(node)
            why = _BANNED_NAMES.get(name or "")
            if why is not None:
                out.append(Diagnostic(
                    path, node.lineno, "RL005",
                    f"impure `{name}` in a Pallas kernel module — {why}"))
    return out
