"""exception discipline: no bare excepts, classify guarded faults.

Two checks:

1. **No bare ``except:``** anywhere in the package or tools — a bare
   handler swallows ``KeyboardInterrupt``/``SystemExit`` and turns a
   dead decode loop into a silent hang.

2. **Guarded-site classification** (``engine/`` + ``scheduler/``): an
   ``except`` handler whose ``try`` body runs a
   ``dispatch_guard``/watchdog call must route the exception through
   the fault classes — reference ``faults.is_transient`` /
   ``is_fatal_device`` / ``classify``, delegate to a classify-routing
   helper (``_fail_streams`` / ``_recover``), or re-``raise``.  A
   handler that reacts identically to a poison request and a dead
   device is how a client input ends up opening a circuit breaker
   (the r18 batcher finding was exactly this).

Waive with ``# graftlint: except(<reason>)`` on the handler line.
"""

from __future__ import annotations

import ast

from ..core import Context, Finding, callee_name, dotted_name

_CLASSIFY_NAMES = {
    "is_transient", "is_fatal_device", "classify", "classify_exception",
    "_fail_streams", "_recover",
}
_GUARD_SCOPES = (
    "mlmicroservicetemplate_tpu/engine/",
    "mlmicroservicetemplate_tpu/scheduler/",
)


def _has_guard_call(nodes: list[ast.stmt]) -> bool:
    for stmt in nodes:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                name = callee_name(node)
                if name in ("dispatch_guard", "guard") or (
                    name == "run"
                    and "watchdog" in dotted_name(node.func).lower()
                ):
                    return True
    return False


def _handler_classifies(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, (ast.Name, ast.Attribute)):
            name = node.attr if isinstance(node, ast.Attribute) else node.id
            if name in _CLASSIFY_NAMES:
                return True
    return False


def _catches_broadly(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    names = []
    if isinstance(t, ast.Tuple):
        names = [dotted_name(e) or getattr(e, "id", "") for e in t.elts]
    else:
        names = [dotted_name(t) or getattr(t, "id", "")]
    return any(n.split(".")[-1] in ("Exception", "BaseException")
               for n in names)


class ExceptionDisciplineRule:
    id = "exception-discipline"
    waiver = "except"
    doc = ("no bare except:; broad handlers around guarded dispatches "
           "must classify via engine.faults (or re-raise)")

    def applies(self, rel: str) -> bool:
        return rel.startswith(("mlmicroservicetemplate_tpu/", "tools/"))

    def check(self, ctx: Context) -> list[Finding]:
        findings: list[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                findings.append(Finding(
                    self.id, ctx.rel, node.lineno,
                    "bare `except:` — swallows KeyboardInterrupt/"
                    "SystemExit; catch Exception (or narrower)",
                ))
        if not ctx.rel.startswith(_GUARD_SCOPES):
            return findings
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Try):
                continue
            if not _has_guard_call(node.body):
                continue
            for handler in node.handlers:
                if not _catches_broadly(handler):
                    continue
                if _handler_classifies(handler):
                    continue
                findings.append(Finding(
                    self.id, ctx.rel, handler.lineno,
                    "broad handler around a guarded dispatch reacts "
                    "identically to poison input and dead devices — "
                    "route through faults.is_transient/is_fatal_device "
                    "(or re-raise / waive: # graftlint: except(reason))",
                ))
        return findings
