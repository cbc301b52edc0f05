"""span-discipline pass: tracing instrumentation contracts (GL11xx).

The obs/ span tracer (ISSUE 4) gives every query a span tree whose
vocabulary downstream consumers — `tools/obs_dump.py`, bench artifact
diffing, the slow-query log, dashboards scraping phase histograms —
match on BY NAME.  Two contracts keep that vocabulary auditable:

* **GL1101** — every `span(...)` call (and `span_around(...)`, its
  decorator form, and `Tracer.early_span(...)`) in the execution/
  resilience/serving modules must name a registered `SPAN_*` constant from
  `spark_druid_olap_tpu/obs/trace.py` (resolved through imports by the
  project layer, so `span(SPAN_H2D)` and a literal `span("h2d")` both
  verify).  Ad-hoc or dynamically-built names fragment the taxonomy and
  silently break every name-matching consumer.
  The same holds for `device_scope(...)`, which names a part of a traced
  program in the HLO metadata a profiler trace shows: its argument must
  be a registered `SCOPE_*` constant of the same module
  (`tools/trace_scopes.py` and the mesh's `sdol.boundary_merge` readers
  match on those names).
* **GL1102** — spans are opened ONLY through the `span(...)` context
  manager: direct calls to the pairing internals
  (`QueryTrace.start_span` / `end_span`) leak an open span on every
  early return or raise between the pair, corrupting the tree for the
  whole query.  The context manager owns the pairing; nothing outside
  obs/ may hand-roll it.

Silent-when-unresolvable does NOT apply to GL1101's name argument: a
span name the project layer cannot resolve to a static string is itself
the violation (the registry is the point), so dynamic names are
reported, not skipped.  When the registry module is absent from the
scanned tree (partial runs) the name check stays silent — there is no
set to verify against — while GL1102 still applies.
"""

from __future__ import annotations

import ast
from typing import Dict, Optional, Set, Tuple

from ..core import LintPass, ModuleContext, call_name

_PAIRING_INTERNALS = ("start_span", "end_span")


class SpanDisciplinePass(LintPass):
    name = "span-discipline"
    default_config = {
        # the instrumented surface the span-name contract covers
        "include": (
            "spark_druid_olap_tpu/exec/",
            "spark_druid_olap_tpu/parallel/",
            "spark_druid_olap_tpu/resilience.py",
            "spark_druid_olap_tpu/api.py",
            "spark_druid_olap_tpu/server.py",
        ),
        # where the registered span-name and device-scope constants live
        "registry_module": "spark_druid_olap_tpu/obs/trace.py",
        "constant_prefix": "SPAN_",
        "scope_prefix": "SCOPE_",
    }

    def __init__(self, config=None):
        super().__init__(config)
        self._registered_cache: Dict[str, Optional[Set[str]]] = {}

    # -- registry resolution --------------------------------------------------

    def _registered(self, prefix: str) -> Optional[Set[str]]:
        """String values of every `<prefix>*` module constant in the
        registry module; None when the registry module is not in the
        scanned tree."""
        if prefix in self._registered_cache:
            return self._registered_cache[prefix]
        names: Set[str] = set()
        mod = (
            self.project.modules.get(self.config["registry_module"])
            if self.project is not None else None
        )
        for cname, expr in (mod.constants.items() if mod else ()):
            if (
                cname.startswith(prefix)
                and isinstance(expr, ast.Constant)
                and isinstance(expr.value, str)
            ):
                names.add(expr.value)
        self._registered_cache[prefix] = names or None
        return self._registered_cache[prefix]

    def _name_kind(self, name: str, canon: str) -> Optional[Tuple[str, str]]:
        """(what the call names, the registry prefix it is held to), or
        None for a call this pass does not police."""
        # `span(NAME)`, its decorator form `span_around(NAME)` and the
        # tracer's `early_span(NAME)` all take the name first
        last = name.rsplit(".", 1)[-1]
        if last in ("span", "span_around", "early_span") or canon.endswith(
            ("obs.span", "obs.trace.span")
        ):
            return "span", self.config["constant_prefix"]
        if last == "device_scope":
            return "device scope", self.config["scope_prefix"]
        return None

    # -- handlers -------------------------------------------------------------

    def on_Call(self, node: ast.Call, ctx: ModuleContext):
        if self.project is None:
            return
        module = self.project.modules.get(ctx.relpath)
        if module is None:
            return
        name = call_name(node)
        if not name:
            return
        canon = self.project.canonical(module, name)
        if canon.rsplit(".", 1)[-1] in _PAIRING_INTERNALS:
            self.report(
                ctx, node, "GL1102",
                "manually paired span call (start_span/end_span): an early "
                "return or raise between the pair leaks an open span and "
                "corrupts the query's tree — open spans ONLY through the "
                "`with span(NAME):` context manager (obs/trace.py)",
            )
            return
        kind = self._name_kind(name, canon)
        if kind is None:
            return
        what, prefix = kind
        registered = self._registered(prefix)
        if registered is None:
            return  # registry module not in this run's scope
        arg = node.args[0] if node.args else None
        if arg is None:
            for kw in node.keywords:
                if kw.arg == "name":
                    arg = kw.value
                    break
        if arg is None:
            self.report(
                ctx, node, "GL1101",
                f"{what} call without a name argument",
            )
            return
        val = self.project.resolve_string(module, arg)
        if val is None:
            self.report(
                ctx, node, "GL1101",
                f"{what} name is not a statically-resolvable string — name "
                f"it with a registered {prefix}* constant from obs/trace.py "
                "(dynamic names fragment the taxonomy every trace consumer "
                "matches on)",
            )
        elif val not in registered:
            self.report(
                ctx, node, "GL1101",
                f"{what} name {val!r} is not in the registered set "
                f"(obs/trace.py {prefix}* constants) — register the "
                "constant first, then use it",
            )
