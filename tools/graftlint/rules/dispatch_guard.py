"""dispatch-guard coverage: every device dispatch rides the guard.

The r9 fault-tolerance layer (engine/faults.py) only sees dispatches
that flow through ``InferenceEngine.dispatch_guard(site, fn)`` — the
watchdog deadline, transient retries, fault injection, per-site host
attribution (``dispatch_host_seconds{site}``) and the fleet breaker
hooks all live there.  A dispatch that bypasses it is invisible to
every one of them: the r8 "legacy path" routing bug was exactly this
class (streams silently served outside the deadline queue), and an
unguarded fetch can wedge the decode loop forever with the watchdog
none the wiser.

This rule flags calls inside ``engine/`` and ``scheduler/`` that hit a
device-dispatch surface — registry decode/prefill executables
(``generate_chunk*``, ``prefill_chunk*``), the repo's
immediately-invoked jit accessors (``programs.paged_chunk_fn()(…)``,
``programs.paged_handoff_fn()(…)``, … of ``engine/programs.py``) and host↔device syncs
(``jax.device_get`` / ``device_put`` / ``block_until_ready``) — unless
the call sits inside a callable passed to ``dispatch_guard`` (or the
watchdog's ``run``), or carries an explicit waiver::

    # graftlint: unguarded(<why this site is exempt>)

Three structural exemptions, by construction rather than waiver:

- calls inside a function handed to ``jax.jit`` (or a ``lax`` control-
  flow body nested in one) are TRACE-TIME composition, not host
  dispatches — the dispatch is wherever the jitted callable is later
  invoked;
- calls inside the definition of a dispatch surface itself (e.g.
  ``run_batch``'s internals, ``start_fused``): the guard belongs at
  the CALL boundary, where the site label is known;
- calls inside warm-up functions (``warmup`` / ``warm`` / ``_warm_*``):
  pre-serving by construction — boot/spawn failures are owned by the
  supervisor and the scaling governor, and guarding them would
  re-number every deterministic ``FAULT_SPEC`` schedule the chaos
  suites have pinned since r9.
"""

from __future__ import annotations

import ast
import re

from ..core import Context, Finding, callee_name, dotted_name

# Immediately-invoked jit-accessor idiom of engine/programs.py:
# ``self.programs.paged_chunk_fn()(…)``.
_ACCESSOR_RE = re.compile(
    r"^_?[a-z0-9_]*(chunk|prefill|handoff|scatter|gather|swap)"
    r"[a-z0-9_]*_fn$"
)
# Direct dispatch / sync surfaces.
_DIRECT_RE = re.compile(
    r"^(generate_chunk\w*|prefill_chunk\w*|"
    r"paged_prefill\w*|device_get|device_put|block_until_ready|"
    r"_gen_chunk|_spec_chunk|_start|start_fused|_start_prefixed\w*|"
    r"run_batch)$"
)

_WARM_RE = re.compile(r"^_?warm")

_SCOPES = (
    "mlmicroservicetemplate_tpu/engine/",
    "mlmicroservicetemplate_tpu/scheduler/",
)
# The guard machinery itself dispatches bare by definition.
_EXEMPT_FILES = {"mlmicroservicetemplate_tpu/engine/faults.py"}
_EXEMPT_FUNCS = {"dispatch_guard"}


def _is_dispatch_call(node: ast.Call) -> str | None:
    """The matched surface name, or None."""
    func = node.func
    if isinstance(func, ast.Call):
        inner = callee_name(func)
        if _ACCESSOR_RE.match(inner):
            return f"{inner}()"
        return None
    name = callee_name(node)
    if _DIRECT_RE.match(name):
        return name
    return None


class DispatchGuardRule:
    id = "dispatch-guard"
    waiver = "unguarded"
    doc = ("device dispatches in engine//scheduler/ must run under "
           "dispatch_guard(site, ...) — else the watchdog, fault "
           "injection, breaker and attribution never see them")

    def applies(self, rel: str) -> bool:
        return (
            rel.startswith(_SCOPES) and rel not in _EXEMPT_FILES
        )

    def check(self, ctx: Context) -> list[Finding]:
        guarded_ids: set[int] = set()
        guarded_fn_names: set[str] = set()
        traced_ids: set[int] = set()
        traced_fn_names: set[str] = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = callee_name(node)
            is_guard = name in ("dispatch_guard", "guard") or (
                name == "run"
                and "watchdog" in dotted_name(node.func).lower()
            )
            is_trace = name in ("jit", "while_loop", "scan", "cond",
                                "fori_loop")
            if not (is_guard or is_trace):
                continue
            ids = guarded_ids if is_guard else traced_ids
            names = guarded_fn_names if is_guard else traced_fn_names
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    names.add(arg.id)
                for sub in ast.walk(arg):
                    ids.add(id(sub))

        findings: list[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            surface = _is_dispatch_call(node)
            if surface is None:
                continue
            if id(node) in guarded_ids or id(node) in traced_ids:
                continue
            skip = False
            for anc in ctx.ancestors(node):
                if not isinstance(anc, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    continue
                if (
                    anc.name in guarded_fn_names
                    or anc.name in traced_fn_names
                    or anc.name in _EXEMPT_FUNCS
                    or _DIRECT_RE.match(anc.name)  # the surface itself
                    or _WARM_RE.match(anc.name)    # pre-serving warm-up
                ):
                    skip = True
                    break
            if skip:
                continue
            findings.append(Finding(
                self.id, ctx.rel, node.lineno,
                f"device dispatch `{surface}` outside dispatch_guard — "
                f"the watchdog/fault-injector/attribution never see it "
                f"(wrap it, or waive: # graftlint: unguarded(reason))",
                end_line=getattr(node, "end_lineno", node.lineno),
            ))
        return findings
