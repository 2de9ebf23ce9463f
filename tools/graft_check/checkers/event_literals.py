"""event-type-literal: cluster event types come from the constants module.

The cluster event plane (_private/events.py + the GCS ring) carries typed
records whose `etype` strings cross process boundaries twice: once on the
`cluster_events_report` flush from controller processes to the GCS, and
again on every `list_events` read (CLI `--type` filters, dashboard query
params, README event-type table). A producer spelling "node.leave" while a filter
spells "node.left" silently matches nothing — so every type a producer may
emit is enumerated as an `EVENT_*` name in `_private/constants.py`, and
emit sites must pass those names, never a re-spelled literal.

The check flags any string literal (or f-string) passed as the event-type
argument to `emit_event(...)`, `self._emit_event(...)`, or
`make_event(...)` outside the constants module itself. Same shape as the
`rpc-method-literal` invariant: one definition, imported everywhere.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Tuple

from tools.graft_check.core import Checker, Finding, ParsedModule, call_target

EVENT_LITERAL_ID = "event-type-literal"

#: the one module allowed to spell event-type strings.
EVENT_NAME_MODULES = ("_private/constants.py",)

_EMIT_FNS = {"emit_event", "_emit_event", "make_event"}


def _etype_arg(call: ast.Call):
    """The event-type argument: first positional, or etype= keyword."""
    if call.args:
        return call.args[0]
    return next((k.value for k in call.keywords if k.arg == "etype"), None)


class EventLiteralChecker(Checker):
    ids = (
        (EVENT_LITERAL_ID,
         "cluster event types passed to emit_event()/make_event() must be "
         "EVENT_* names from the shared constants module, not re-spelled "
         "literals"),
    )

    def __init__(self, event_name_modules: Tuple[str, ...] =
                 EVENT_NAME_MODULES):
        self._event_modules = tuple(event_name_modules)

    def check_module(self, mod: ParsedModule) -> Iterable[Finding]:
        if any(mod.relpath.endswith(m) for m in self._event_modules):
            return ()
        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            _base, attr = call_target(node)
            if attr not in _EMIT_FNS:
                continue
            arg = _etype_arg(node)
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                out.append(mod.finding(
                    EVENT_LITERAL_ID, node,
                    f"event type {arg.value!r} spelled as a literal at an "
                    f"emit site — import the EVENT_* name from "
                    f"ray_tpu._private.constants (producers and list_events "
                    f"filters must share one vocabulary)"))
            elif isinstance(arg, ast.JoinedStr):
                out.append(mod.finding(
                    EVENT_LITERAL_ID, node,
                    "event type built from an f-string at an emit site — "
                    "event types are a closed vocabulary (constants.py "
                    "EVENT_TYPES); put variability in the event's fields, "
                    "not its type"))
        return out
