"""Check: socket-without-timeout.

A socket without a configured timeout is an unbounded blocking call
waiting to strand a thread: the healthmon hang-proof probe, the
failover watchdog, and the verify-plane breaker all exist
because "it will answer eventually" is not an invariant this codebase
gets to assume.  This check makes the discipline lexical:

  * ``socket.create_server(...)`` / ``socket.socket(...)`` creations
    and ``socket.create_connection(...)`` without a timeout argument
    (2nd positional or ``timeout=``) are flagged unless the enclosing
    function — or any method of the enclosing class — configures a
    timeout (``settimeout`` / ``setdefaulttimeout``): the common idioms
    are create-then-settimeout in one function, or a connection class
    whose constructor dials with a timeout and whose other methods
    read.
  * ``.recv(...)`` / ``.recv_into(...)`` calls, and ``.connect(...)``
    on a socket-named receiver, are flagged under the same scope rule —
    a read helper in a class that never configures a timeout is exactly
    the stranded-thread shape.

``settimeout(None)`` clears the check too: deliberately blocking IO is
allowed, but it must be DECLARED, not inherited silently from the
socket default.  The intentional blocking accept-loop listeners
(p2p/abci/rpc/privval) are suppressed via justified allowlist entries
per policy — an accept loop woken by ``netutil.close_socket``'s
shutdown() is a reviewed pattern, not an accident.
"""

from __future__ import annotations

import ast

from .linter import Finding, Module, dotted_name, keyword_names, terminal_name

CHECK_ID = "socket-without-timeout"
SUMMARY = "socket created or read without a configured timeout in scope"

_RECV_NAMES = ("recv", "recv_into")
_CONFIG_NAMES = ("settimeout", "setdefaulttimeout")
_SOCKY = ("sock", "listener", "conn")


def _has_timeout_arg(call: ast.Call) -> bool:
    """create_connection((host, port), timeout) / timeout= kw."""
    return len(call.args) >= 2 or "timeout" in keyword_names(call)


def _configures_timeout(scope: ast.AST) -> bool:
    for n in ast.walk(scope):
        if not isinstance(n, ast.Call):
            continue
        t = terminal_name(n.func)
        if t in _CONFIG_NAMES:
            return True
        if t == "create_connection" and _has_timeout_arg(n):
            return True
    return False


def _receiver_is_socky(call: ast.Call) -> bool:
    """``x.connect(...)`` where x's terminal name smells like a socket —
    keeps sqlite3.connect / pg.connect / db-handle false positives out
    while still catching ``self._sock.connect(...)``."""
    if not isinstance(call.func, ast.Attribute):
        return False
    recv = terminal_name(call.func.value)
    if recv is None:
        return False
    low = recv.lower()
    return any(s in low for s in _SOCKY)


def check(mod: Module) -> list[Finding]:
    findings: list[Finding] = []
    clears: dict[int, bool] = {}  # id(scope node) -> configures a timeout

    def cleared(stack: list[ast.AST]) -> bool:
        for scope in stack:
            key = id(scope)
            if key not in clears:
                clears[key] = _configures_timeout(scope)
            if clears[key]:
                return True
        return False

    def visit(node: ast.AST, stack: list[ast.AST]) -> None:
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ):
            stack = stack + [node]
        if isinstance(node, ast.Call):
            dn = dotted_name(node.func)
            t = terminal_name(node.func)
            msg = None
            if dn == "socket.socket":
                msg = (
                    "socket.socket(...) with no settimeout() in the "
                    "enclosing function/class — an unbounded blocking "
                    "socket; declare the timeout (settimeout(None) if "
                    "blocking is intended)"
                )
            elif t == "create_server" and (
                dn is None or dn.startswith("socket.")
            ):
                msg = (
                    "socket.create_server(...) listener with no "
                    "settimeout() in scope — accept() will block "
                    "unboundedly; set a poll timeout or allowlist the "
                    "intentional blocking accept loop"
                )
            elif t == "create_connection" and not _has_timeout_arg(node):
                msg = (
                    "socket.create_connection(...) without a timeout "
                    "argument — the dial can hang a thread forever"
                )
            elif t in _RECV_NAMES and isinstance(node.func, ast.Attribute):
                msg = (
                    f".{t}(...) with no timeout configured in the "
                    "enclosing function/class — a dead peer strands "
                    "this thread; settimeout() first (None if blocking "
                    "is deliberate)"
                )
            elif t == "connect" and _receiver_is_socky(node):
                msg = (
                    ".connect(...) on a socket with no timeout "
                    "configured in scope — the dial can hang forever"
                )
            if msg is not None and not cleared(stack):
                findings.append(
                    Finding(CHECK_ID, mod.path, node.lineno,
                            node.col_offset, msg)
                )
        for child in ast.iter_child_nodes(node):
            visit(child, stack)

    # the stack starts EMPTY (not the module): a settimeout in one
    # class must not launder every other class in the same file
    visit(mod.tree, [])
    return findings
