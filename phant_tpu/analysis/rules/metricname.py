"""METRICNAME: one static gate for the metric-name/help catalog.

Absorbs the name/help checks of the old runtime `scripts/metrics_lint.py`
into the analyzer (the script is now a thin shim over this rule), so the
exposition checker and the static checker cannot drift apart:

  * M1 — a registry call (`metrics.count/gauge_set/gauge_add/observe/
    observe_hist/phase`, and `observe_split`, which names two families)
    whose metric name is not a string literal:
    dynamic names are a cardinality hazard and invisible to this gate
    (annotate the few legitimate sites, e.g. names drawn from an adjacent
    literal table).
  * M2 — a literal name that is not `[a-z0-9_.]+`: the Prometheus
    sanitizer (`trace.prometheus_name`) would mangle it lossily.
  * M3 — a literal name with no entry in `trace.METRIC_HELP`: every
    exported family documents itself or the gate is red.
  * M4 — catalog rot: a `METRIC_HELP` key that appears nowhere in the
    package as a string literal is a dead catalog entry.

The catalog is read from the module that defines `METRIC_HELP` (the
metrics registry module, phant_tpu/utils/trace.py in this repo) — found
by scanning, so fixture packages in tests can carry their own.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, Optional, Set, Tuple

from phant_tpu.analysis.core import Finding, Rule, iter_calls
from phant_tpu.analysis.rules._taint import snippet
from phant_tpu.analysis.symbols import ModuleInfo, Project, _dotted

_NAME_RE = re.compile(r"^[a-z0-9_.]+$")
_METHODS = ("count", "gauge_set", "gauge_add", "observe", "observe_hist", "phase", "observe_split")


class MetricNameRule(Rule):
    name = "METRICNAME"
    description = "metric names: literal, sanitizable, and in METRIC_HELP"

    def run(self, project: Project) -> Iterator[Finding]:
        catalog = self._find_catalog(project)
        if catalog is None:
            return
        cat_module, help_node, keys = catalog
        used: Set[str] = set()
        for mi in project.modules.values():
            in_catalog = mi.name == cat_module.name
            for node in ast.walk(mi.tree):
                if (
                    isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and not self._inside(help_node, node, in_catalog)
                ):
                    used.add(node.value)
            if in_catalog:
                continue  # the registry implementation passes names through
            yield from self._check_sites(project, mi, cat_module.name, keys)
        for key, lineno in sorted(keys.items()):
            if key not in used:
                yield Finding(
                    rule=self.name,
                    path=self._rel(cat_module),
                    line=lineno,
                    col=1,
                    message=(
                        f"METRIC_HELP entry {key!r} is never emitted anywhere "
                        "in the package — dead catalog entry (or the emit "
                        "site builds the name dynamically: make it literal)"
                    ),
                    context=f"{cat_module.name}.METRIC_HELP",
                )

    @staticmethod
    def _rel(mi: ModuleInfo) -> str:
        from phant_tpu.analysis.core import rel_path

        return rel_path(mi.path)

    @staticmethod
    def _inside(help_node: ast.AST, node: ast.AST, same_module: bool) -> bool:
        if not same_module:
            return False
        return (
            getattr(node, "lineno", 0) >= help_node.lineno
            and getattr(node, "end_lineno", 0) <= (help_node.end_lineno or 0)
        )

    def _find_catalog(
        self, project: Project
    ) -> Optional[Tuple[ModuleInfo, ast.AST, Dict[str, int]]]:
        for mi in project.modules.values():
            for node in mi.tree.body:
                target = None
                if isinstance(node, ast.Assign) and len(node.targets) == 1:
                    target, value = node.targets[0], node.value
                elif isinstance(node, ast.AnnAssign):
                    target, value = node.target, node.value
                else:
                    continue
                if (
                    isinstance(target, ast.Name)
                    and target.id == "METRIC_HELP"
                    and isinstance(value, ast.Dict)
                ):
                    keys = {
                        k.value: k.lineno
                        for k in value.keys
                        if isinstance(k, ast.Constant) and isinstance(k.value, str)
                    }
                    return mi, node, keys
        return None

    def _check_sites(
        self, project: Project, mi: ModuleInfo, cat_module: str, keys: Dict[str, int]
    ) -> Iterator[Finding]:
        for call in iter_calls(mi.tree):
            for name_arg in self._metric_name_args(mi, call, cat_module):
                yield from self._check_name(project, mi, call, name_arg, keys)

    def _check_name(
        self,
        project: Project,
        mi: ModuleInfo,
        call: ast.Call,
        name_arg: ast.AST,
        keys: Dict[str, int],
    ) -> Iterator[Finding]:
        if not (
            isinstance(name_arg, ast.Constant)
            and isinstance(name_arg.value, str)
        ):
            yield self.finding(
                project,
                mi,
                call,
                f"`{snippet(call)}` uses a non-literal metric name — "
                "dynamic names defeat the static catalog gate and risk "
                "unbounded cardinality",
                context=mi.name,
            )
            return
        name = name_arg.value
        if not _NAME_RE.match(name):
            yield self.finding(
                project,
                mi,
                call,
                f"metric name {name!r} is not [a-z0-9_.]+ — the "
                "Prometheus family sanitization would be lossy",
                context=mi.name,
            )
        if name not in keys:
            yield self.finding(
                project,
                mi,
                call,
                f"metric name {name!r} has no METRIC_HELP entry — add "
                "its help string to the registry catalog",
                context=mi.name,
            )

    def _metric_name_args(
        self, mi: ModuleInfo, call: ast.Call, cat_module: str
    ) -> Tuple[ast.AST, ...]:
        """The metric-name argument(s) of a registry call — positional OR
        `name=` keyword (a keyword-only dynamic name must not slip past
        M1); `observe_split` names two families, its first two arguments
        — else () for non-registry calls. A registry call whose
        name cannot be located at all (e.g. `metrics.count(**kw)`) yields
        the call node itself, which is non-literal and so flags as M1."""
        func = call.func
        is_registry = False
        if isinstance(func, ast.Attribute) and func.attr in _METHODS:
            d = _dotted(func.value)
            if d is not None:
                head, _, rest = d.partition(".")
                full = mi.imports.get(head, head) + ("." + rest if rest else "")
                is_registry = full == f"{cat_module}.metrics" or d == "metrics"
        elif isinstance(func, ast.Name):
            is_registry = mi.imports.get(func.id) == f"{cat_module}.phase"
        if not is_registry:
            return ()
        if isinstance(func, ast.Attribute) and func.attr == "observe_split":
            return tuple(call.args[:2]) if len(call.args) >= 2 else (call,)
        if call.args:
            return (call.args[0],)
        for kw in call.keywords:
            if kw.arg == "name":
                return (kw.value,)
        return (call,)
