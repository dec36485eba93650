"""Check: host-sync-in-hot-path.

A host<->device synchronization inside ``ops/`` or ``parallel/`` —
``.block_until_ready()``, ``jax.device_get``, ``.item()``, or
``np.asarray``/``np.array`` materializing a device value — stalls the
dispatch pipeline: one stray fetch is a full device round trip, and it
serializes work the async dispatch model exists to overlap.  The verify plane's contract is that device results
are fetched at ONE declared place per pipeline (the collect boundary);
everywhere else in the hot path a sync is a bug.

Declared boundaries live in ``kernel_manifest.COLLECT_BOUNDARIES``
(``path::function`` with a justification); anything inside such a
function is exempt.  ``np.asarray``/``np.array`` over a literal
(list/tuple/comprehension/constant) is host constant construction — the
SHA round-constant tables, limb weights — and never flagged; neither is
``np.array`` over a host device list (an expression containing a
``devices()`` call, or a local name assigned from one — the
``parallel/mesh.py`` factories), which wraps host objects, not device
arrays.  The jitted counterpart ``jnp.asarray`` is an async H2D
transfer, not a sync, and is not this check's business.
"""

from __future__ import annotations

import ast

from . import kernel_manifest as manifest
from .linter import Finding, Module, dotted_name, terminal_name

CHECK_ID = "host-sync-in-hot-path"
SUMMARY = "device sync/fetch in ops//parallel/ outside a declared collect boundary"

SCOPE_DIRS = {"ops", "parallel"}

_NP_MODULES = {"np", "numpy"}
_NP_MATERIALIZERS = {"asarray", "array"}
_LITERAL_NODES = (
    ast.Constant, ast.List, ast.Tuple, ast.Set, ast.Dict,
    ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp,
)


class _Visitor(ast.NodeVisitor):
    def __init__(self, mod: Module):
        self.mod = mod
        self.findings: list[Finding] = []
        self._fn_stack: list[str] = []
        # per-scope names assigned from a host device list (module scope
        # at index 0, one set per enclosing function above it)
        self._device_names: list[set[str]] = [set()]

    def _is_device_list(self, node: ast.expr) -> bool:
        """True when the expression builds or references a host device
        list: a ``devices()`` call anywhere in the subtree, or a name a
        visible scope assigned from one."""
        for n in ast.walk(node):
            if isinstance(n, ast.Call) and terminal_name(n.func) == "devices":
                return True
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) and any(
                n.id in scope for scope in self._device_names
            ):
                return True
        return False

    def visit_Assign(self, node: ast.Assign):  # noqa: N802
        names = [
            n.id
            for t in node.targets
            for n in ast.walk(t)
            if isinstance(n, ast.Name)
        ]
        if self._is_device_list(node.value):
            self._device_names[-1].update(names)
        else:
            # reassignment to anything else ends the exemption
            self._device_names[-1].difference_update(names)
        self.generic_visit(node)

    def _exempt(self) -> bool:
        return any(
            manifest.collect_boundary(self.mod.path, name)
            for name in self._fn_stack
        )

    def _add(self, node: ast.AST, what: str) -> None:
        where = self._fn_stack[-1] if self._fn_stack else "<module>"
        self.findings.append(
            Finding(
                CHECK_ID, self.mod.path, node.lineno, node.col_offset,
                f"{what} in {where!r} — hot-path host sync; move the fetch "
                "to a declared collect boundary (or register this function "
                "in kernel_manifest.COLLECT_BOUNDARIES with a justification)",
            )
        )

    def _visit_fn(self, node):
        self._fn_stack.append(node.name)
        self._device_names.append(set())
        self.generic_visit(node)
        self._device_names.pop()
        self._fn_stack.pop()

    visit_FunctionDef = _visit_fn  # noqa: N815
    visit_AsyncFunctionDef = _visit_fn  # noqa: N815

    def visit_Call(self, node: ast.Call):  # noqa: N802
        if not self._exempt():
            tn = terminal_name(node.func)
            d = dotted_name(node.func) or ""
            if tn == "block_until_ready":
                self._add(node, ".block_until_ready()")
            elif tn == "device_get" and (
                d in ("jax.device_get", "device_get") or d.endswith(".device_get")
            ):
                self._add(node, "jax.device_get()")
            elif tn == "item" and not node.args:
                self._add(node, ".item()")
            elif (
                tn in _NP_MATERIALIZERS
                and isinstance(node.func, ast.Attribute)
                and dotted_name(node.func.value) in _NP_MODULES
                and node.args
                and not isinstance(node.args[0], _LITERAL_NODES)
                and not self._is_device_list(node.args[0])
            ):
                self._add(node, f"np.{tn}() on a non-literal value")
        self.generic_visit(node)


def check(mod: Module) -> list[Finding]:
    if not SCOPE_DIRS.intersection(mod.parts[:-1]):
        return []
    v = _Visitor(mod)
    v.visit(mod.tree)
    return v.findings
