"""Observability family: PALP301 unregistered metric/span names.

Scope: ``src/repro/core/`` — the layer whose spans and metrics feed
``tools/palpascope.py``.

Palpascope keys every breakdown (per-span-kind latency, per-metric
snapshots) by a *closed vocabulary*: the ``SPAN_*`` / ``EVENT_*`` /
``METRIC_*`` constants in :mod:`repro.core.obs`.  A span or metric
named with an f-string (``tr.span(f"rpc_{node}", ...)``) explodes
label cardinality — every node id becomes its own kind — and a bare
string literal drifts away from the registered table silently.  The
rule requires the name argument of every observability call to be one
of the registered constants (a ``SPAN_``/``EVENT_``/``METRIC_``-
prefixed name, possibly module-qualified like ``obs.SPAN_RPC``).
"""

from __future__ import annotations

import ast

from ..diagnostics import Diagnostic
from ..registry import FileContext, Rule, register

#: receiver names an observability call is recognized by (by convention
#: tracers are bound to ``tr``/``tracer``/``<obj>.tracer``, registries
#: to ``metrics``/``registry``/``<obj>.metrics`` and the host profile to
#: ``host_profile``/``obs.host_profile``)
_RECEIVERS = {"tr", "tracer", "metrics", "registry", "host_profile"}
_RECEIVER_ATTRS = {"tracer", "metrics", "host_profile"}

#: the name-taking observability methods (first positional argument is
#: a span kind, event name, or metric name)
_METHODS = {"start", "span", "event", "counter", "gauge", "histogram",
            "count"}

_PREFIXES = ("SPAN_", "EVENT_", "METRIC_")


def _core_scope(path: str) -> bool:
    return path.startswith("src/repro/core/")


def _is_obs_receiver(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return node.id in _RECEIVERS
    if isinstance(node, ast.Attribute):
        return node.attr in _RECEIVER_ATTRS
    return False


def _is_registered_constant(arg: ast.AST) -> bool:
    """A ``SPAN_``/``EVENT_``/``METRIC_``-prefixed name, bare or
    module-qualified (``SPAN_RPC``, ``obs.SPAN_RPC``)."""
    if isinstance(arg, ast.Name):
        return arg.id.startswith(_PREFIXES)
    if isinstance(arg, ast.Attribute):
        return arg.attr.startswith(_PREFIXES)
    return False


def _check_unregistered_names(ctx: FileContext) -> list[Diagnostic]:
    out = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _METHODS
                and _is_obs_receiver(node.func.value)
                and node.args):
            continue
        name = node.args[0]
        if _is_registered_constant(name):
            continue
        what = ("f-string" if isinstance(name, ast.JoinedStr)
                else "string literal" if isinstance(name, ast.Constant)
                else "computed name")
        out.append(Diagnostic(
            ctx.path, name.lineno, name.col_offset + 1, "PALP301",
            f"{what} as `.{node.func.attr}()` name: span/metric names "
            "in src/repro/core must be registered SPAN_*/EVENT_*/"
            "METRIC_* constants (repro.core.obs) so palpascope's "
            "vocabulary stays closed and cardinality finite"))
    return out


register(Rule(
    code="PALP301",
    name="unregistered-metric-name",
    family="observability",
    summary=("span/event/metric names in src/repro/core must be the "
             "registered SPAN_*/EVENT_*/METRIC_* constants — no "
             "f-strings or ad-hoc literals (cardinality stays finite)"),
    scope=_core_scope,
    check=_check_unregistered_names,
))
