"""Contract-index extraction: every string-keyed producer and consumer.

The stack's wire surface is held together by NAMES — metric families,
event kinds, HTTP routes, SSE frame kinds, schema-version literals,
pinned field tuples — and none of the other
tiers can see two of them drift apart. This module builds the
repo-wide :class:`ContractIndex` the ``contract-*`` rules check:

- **python producers** (stdlib ``ast`` over the already-parsed
  ``ModuleIndex`` trees, zero imports executed): every
  ``metrics.counter/gauge/histogram`` registration with its statically
  resolved family name and label-key set, every ``EventLog.emit`` kind,
  the HTTP route dispatch comparisons and raw client request paths,
  ``_sse(...)`` frame emissions, and ``apex-tpu/...`` schema constants
  with their writer stamps and validator comparisons;
- **python consumers**: literal ``e["kind"] ==`` / ``.get("kind") ==``
  comparisons (NOT ``.kind`` attribute reads — ``FaultSpec.kind`` is a
  fault name, not an event kind) and the SSE client's
  ``event == "..."`` parse arms;
- **text consumers**: the instrument/event catalogs of
  ``docs/observability.md``, the endpoint table of ``docs/http.md``,
  and the family names pinned by ``tests/golden/observability.prom``
  — parsed from their markdown tables / ``# TYPE`` lines.

Same precision bias as every other tier: a name is indexed only when it
is statically resolvable — a string literal, an f-string over a
comprehension/loop variable bound to a literal tuple (possibly a
module-level or imported constant: ``f"serving.{name}" for name in
_RUN_COUNTERS``), or a dict-literal ``.items()`` loop. A
counter/gauge/histogram registration whose name CANNOT be resolved is
itself recorded (``ContractIndex.unresolved_metrics``) — the
undocumented-metric rule reports it, so the wire surface stays
statically auditable by construction. The raw ``metrics.record``
series is deliberately out of scope: it banks run-stats trajectory
keyed by dynamic stats dicts, not cataloged instruments.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Dict, FrozenSet, List, Optional, Sequence, Set, Tuple

from apex_tpu.analysis.walker import ModuleIndex, name_tail

_INSTRUMENT_KINDS = ("counter", "gauge", "histogram")

#: schema-version literals all share this prefix (the artifact
#: namespace); anything matching it in a writer dict is a schema pin
_SCHEMA_PREFIX = "apex-tpu/"

#: a full versioned artifact id (``apex-tpu/<artifact>/v<n>``) — what a
#: schema CONSTANT must hold; bare-prefix strings (validator
#: ``startswith`` literals, this module's own namespace constant) are
#: not themselves schema pins
_SCHEMA_ID_RE = re.compile(r"^apex-tpu/[a-z0-9_.-]+/v\d+$")

#: a metric family name: dotted lowercase words (every real family has
#: at least one dot — ``serving.admitted``, ``pool.host_tier_demotes``)
_FAMILY_RE = re.compile(r"^[a-z_][a-z0-9_]*(?:\.[a-z0-9_]+)+$")

#: an event kind: one lowercase word, optionally dotted
#: (``fleet.alert``)
_EVENT_RE = re.compile(r"^[a-z_][a-z0-9_]*(?:\.[a-z0-9_]+)?$")


@dataclasses.dataclass(frozen=True)
class Site:
    """One source location a contract fact was extracted from."""
    path: str
    line: int
    col: int = 1
    end_line: int = 0
    scope: str = "<module>"


@dataclasses.dataclass(frozen=True)
class MetricSite:
    family: str
    kind: str                        # counter | gauge | histogram
    label_keys: FrozenSet[str]       # statically resolved literal keys
    opaque_labels: bool              # a non-literal labels expr (or
    site: Site = None                # ``**spread``) contributes keys
    #                                  we cannot see


@dataclasses.dataclass(frozen=True)
class RouteSite:
    route: str                       # "/v1/generate" or "/v1/cancel/"
    prefix: bool                     # True for ``path.startswith`` routes
    site: Site = None


@dataclasses.dataclass
class SchemaConst:
    name: str                        # REPORT_SCHEMA
    value: str                       # "apex-tpu/scenario-report/v1"
    site: Site = None
    stamped: bool = False            # a writer dict carries it
    validated: bool = False          # a reader compares against it


@dataclasses.dataclass
class ContractIndex:
    metrics: List[MetricSite] = dataclasses.field(default_factory=list)
    unresolved_metrics: List[Tuple[Site, str]] = \
        dataclasses.field(default_factory=list)
    event_emits: Dict[str, List[Site]] = \
        dataclasses.field(default_factory=dict)
    event_consumers: Dict[str, List[Site]] = \
        dataclasses.field(default_factory=dict)
    routes: List[RouteSite] = dataclasses.field(default_factory=list)
    client_paths: List[Tuple[str, Site]] = \
        dataclasses.field(default_factory=list)
    sse_emits: Dict[str, List[Site]] = \
        dataclasses.field(default_factory=dict)
    sse_parses: Dict[str, List[Site]] = \
        dataclasses.field(default_factory=dict)
    schemas: List[SchemaConst] = dataclasses.field(default_factory=list)
    raw_schema_stamps: List[Tuple[str, Site]] = \
        dataclasses.field(default_factory=list)
    # -- text consumers ----------------------------------------------------
    doc_metrics: Dict[str, Site] = dataclasses.field(default_factory=dict)
    doc_events: Dict[str, Site] = dataclasses.field(default_factory=dict)
    doc_routes: Dict[str, Site] = dataclasses.field(default_factory=dict)
    has_doc_metrics: bool = False    # the catalog section exists at all
    has_doc_events: bool = False
    has_doc_routes: bool = False
    golden_families: Dict[str, Site] = \
        dataclasses.field(default_factory=dict)

    def produced_families(self) -> Dict[str, List[MetricSite]]:
        out: Dict[str, List[MetricSite]] = {}
        for m in self.metrics:
            out.setdefault(m.family, []).append(m)
        return out

def _module_dotted(path: str) -> str:
    mod = path[:-3] if path.endswith(".py") else path
    mod = mod.replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


def _site(mi: ModuleIndex, node: ast.AST) -> Site:
    return Site(path=mi.path, line=getattr(node, "lineno", 1),
                col=getattr(node, "col_offset", 0) + 1,
                end_line=getattr(node, "end_lineno", 0)
                or getattr(node, "lineno", 1),
                scope=mi.scope_of(node))


def _const_str_values(node: ast.AST) -> Optional[Tuple[str, ...]]:
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
            else:
                return None
        return tuple(out)
    return None


class _ModuleConsts:
    """Module-level ``NAME = "str" | ("a", "b", ...)`` constants plus
    the ``from X import NAME`` table — the cross-module half of name
    resolution (``_RUN_COUNTERS`` lives in scheduler.py, the f-string
    that spends it in frontend.py)."""

    def __init__(self, modules: Dict[str, ModuleIndex]):
        self.strs: Dict[str, Dict[str, str]] = {}
        self.tuples: Dict[str, Dict[str, Tuple[str, ...]]] = {}
        self.imports: Dict[str, Dict[str, Tuple[str, str]]] = {}
        for rel, mi in modules.items():
            mod = _module_dotted(rel)
            self.strs[mod] = {}
            self.tuples[mod] = {}
            for node in mi.tree.body:
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)):
                    continue
                name = node.targets[0].id
                if isinstance(node.value, ast.Constant) \
                        and isinstance(node.value.value, str):
                    self.strs[mod][name] = node.value.value
                else:
                    vals = _const_str_values(node.value)
                    if vals is not None:
                        self.tuples[mod][name] = vals
            imp: Dict[str, Tuple[str, str]] = {}
            for entry in mi.imports:
                if entry.attr:
                    src = entry.module
                    if getattr(entry, "level", 0):
                        pkg = mod.rsplit(".", entry.level)[0] \
                            if "." in mod else mod
                        src = f"{pkg}.{entry.module}" \
                            if entry.module else pkg
                    imp[entry.local] = (src, entry.attr)
            self.imports[mod] = imp

    def lookup_tuple(self, module: str, name: str) \
            -> Optional[Tuple[str, ...]]:
        vals = self.tuples.get(module, {}).get(name)
        if vals is not None:
            return vals
        src = self.imports.get(module, {}).get(name)
        if src is not None:
            return self.tuples.get(src[0], {}).get(src[1])
        return None

    def lookup_str(self, module: str, name: str) -> Optional[str]:
        v = self.strs.get(module, {}).get(name)
        if v is not None:
            return v
        src = self.imports.get(module, {}).get(name)
        if src is not None:
            return self.strs.get(src[0], {}).get(src[1])
        return None


class _Resolver:
    """Static string resolution inside one function/comprehension
    context: literals, f-strings, loop variables over literal tuples,
    ``dict.items()`` loops over a local dict literal, and module/
    imported constants. ``resolve`` returns the full set of values an
    expression can take, or None when any part is dynamic."""

    def __init__(self, consts: _ModuleConsts, module: str):
        self.consts = consts
        self.module = module
        self.env: List[Dict[str, Tuple[str, ...]]] = []
        self.local_dicts: Dict[str, Tuple[str, ...]] = {}
        self.local_tuples: Dict[str, Tuple[str, ...]] = {}

    def push(self, binding: Dict[str, Tuple[str, ...]]) -> None:
        self.env.append(binding)

    def pop(self) -> None:
        self.env.pop()

    def _name_values(self, name: str) -> Optional[Tuple[str, ...]]:
        for frame in reversed(self.env):
            if name in frame:
                return frame[name]
        if name in self.local_tuples:
            # a local ``x = "lit"`` binds one value, not an iteration
            vals = self.local_tuples[name]
            if len(vals) == 1:
                return vals
            return None
        v = self.consts.lookup_str(self.module, name)
        return (v,) if v is not None else None

    def iter_values(self, node: ast.AST) -> Optional[Tuple[str, ...]]:
        """Values a ``for x in <node>`` loop binds, when literal."""
        vals = _const_str_values(node)
        if vals is not None:
            return vals
        if isinstance(node, ast.Name):
            for frame in reversed(self.env):
                if node.id in frame:
                    return frame[node.id]
            vals = self.local_tuples.get(node.id)
            if vals is not None:
                return vals
            return self.consts.lookup_tuple(self.module, node.id)
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "items" \
                and isinstance(node.func.value, ast.Name):
            return self.local_dicts.get(node.func.value.id)
        return None

    def resolve(self, node: ast.AST) -> Optional[Set[str]]:
        if isinstance(node, ast.Constant) \
                and isinstance(node.value, str):
            return {node.value}
        if isinstance(node, ast.Name):
            vals = self._name_values(node.id)
            return set(vals) if vals is not None else None
        if isinstance(node, ast.IfExp):
            a = self.resolve(node.body)
            b = self.resolve(node.orelse)
            return a | b if a is not None and b is not None else None
        if isinstance(node, ast.JoinedStr):
            parts: List[Set[str]] = []
            for part in node.values:
                if isinstance(part, ast.Constant):
                    parts.append({str(part.value)})
                elif isinstance(part, ast.FormattedValue):
                    if part.format_spec is not None:
                        return None
                    sub = self.resolve(part.value)
                    if sub is None:
                        return None
                    parts.append(sub)
                else:
                    return None
            out: Set[str] = {""}
            for p in parts:
                out = {a + b for a in out for b in p}
            return out
        return None


def _dict_literal_keys(node: ast.Dict) \
        -> Tuple[FrozenSet[str], bool]:
    keys: Set[str] = set()
    opaque = False
    for k in node.keys:
        if k is None:                      # ``**spread``
            opaque = True
        elif isinstance(k, ast.Constant) and isinstance(k.value, str):
            keys.add(k.value)
        else:
            opaque = True
    return frozenset(keys), opaque


class _ModuleExtractor(ast.NodeVisitor):
    """One pass over one module's tree, maintaining the loop-binding
    environment so names at call sites resolve in context."""

    def __init__(self, mi: ModuleIndex, consts: _ModuleConsts,
                 index: ContractIndex):
        self.mi = mi
        self.index = index
        self.resolver = _Resolver(consts, _module_dotted(mi.path))

    # -- scope bookkeeping -------------------------------------------------

    def _prescan_function(self, node: ast.AST) -> Tuple[dict, dict]:
        """Function-local ``x = {...literal...}`` / ``x = (...)``
        assignments, so ``for name, v in vals.items():`` and
        ``labels=lbl`` resolve."""
        dicts: Dict[str, Tuple[str, ...]] = {}
        tuples: Dict[str, Tuple[str, ...]] = {}
        for sub in ast.walk(node):
            if not (isinstance(sub, ast.Assign)
                    and len(sub.targets) == 1
                    and isinstance(sub.targets[0], ast.Name)):
                continue
            tname = sub.targets[0].id
            if isinstance(sub.value, ast.Dict):
                keys = []
                for k in sub.value.keys:
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str):
                        keys.append(k.value)
                    else:
                        keys = None
                        break
                if keys is not None:
                    dicts[tname] = tuple(keys)
            else:
                vals = _const_str_values(sub.value)
                if vals is not None:
                    tuples[tname] = vals
                elif isinstance(sub.value, ast.Constant) \
                        and isinstance(sub.value.value, str):
                    tuples[tname] = (sub.value.value,)
        return dicts, tuples

    def visit_FunctionDef(self, node):
        saved = (self.resolver.local_dicts, self.resolver.local_tuples)
        d, t = self._prescan_function(node)
        self.resolver.local_dicts = d
        self.resolver.local_tuples = t
        self.generic_visit(node)
        self.resolver.local_dicts, self.resolver.local_tuples = saved

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_For(self, node: ast.For):
        binding: Dict[str, Tuple[str, ...]] = {}
        vals = self.resolver.iter_values(node.iter)
        if vals is not None:
            if isinstance(node.target, ast.Name):
                binding[node.target.id] = vals
            elif isinstance(node.target, ast.Tuple) \
                    and node.target.elts \
                    and isinstance(node.target.elts[0], ast.Name):
                # ``for name, v in vals.items()`` — keys bind first
                binding[node.target.elts[0].id] = vals
        self.resolver.push(binding)
        self.generic_visit(node)
        self.resolver.pop()

    def _visit_comprehension(self, node):
        binding: Dict[str, Tuple[str, ...]] = {}
        for gen in node.generators:
            vals = self.resolver.iter_values(gen.iter)
            if vals is not None and isinstance(gen.target, ast.Name):
                binding[gen.target.id] = vals
        self.resolver.push(binding)
        self.generic_visit(node)
        self.resolver.pop()

    visit_ListComp = _visit_comprehension
    visit_SetComp = _visit_comprehension
    visit_DictComp = _visit_comprehension
    visit_GeneratorExp = _visit_comprehension

    # -- module-level constants --------------------------------------------

    def visit_Assign(self, node: ast.Assign):
        if len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and self.mi.scope_of(node) == "<module>":
            name = node.targets[0].id
            if isinstance(node.value, ast.Constant) \
                    and isinstance(node.value.value, str) \
                    and _SCHEMA_ID_RE.match(node.value.value):
                self.index.schemas.append(SchemaConst(
                    name=name, value=node.value.value,
                    site=_site(self.mi, node)))
        # ``x["schema"] = CONST`` writer stamps
        if len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Subscript):
            sub = node.targets[0]
            if isinstance(sub.slice, ast.Constant) \
                    and sub.slice.value == "schema":
                self._record_schema_stamp(node.value, node)
        self.generic_visit(node)

    # -- the call-site facts -----------------------------------------------

    def visit_Call(self, node: ast.Call):
        func = node.func
        if isinstance(func, ast.Attribute):
            if func.attr in _INSTRUMENT_KINDS \
                    and name_tail(func.value) == "metrics" and node.args:
                self._record_metric(node, func.attr)
            elif func.attr == "emit" \
                    and name_tail(func.value) == "events" and node.args:
                self._record_emit(node)
            elif func.attr == "_sse" and len(node.args) >= 2:
                kinds = self.resolver.resolve(node.args[1])
                if kinds:
                    for kind in kinds:
                        self.index.sse_emits.setdefault(
                            kind, []).append(_site(self.mi, node))
            elif func.attr == "startswith" and node.args:
                lit = node.args[0]
                if isinstance(lit, ast.Constant) \
                        and isinstance(lit.value, str):
                    if lit.value.startswith(_SCHEMA_PREFIX):
                        # prefix validator: validates every schema
                        # const whose value it prefixes
                        for sc in self.index.schemas:
                            if sc.value.startswith(lit.value):
                                sc.validated = True
                        self._pending_schema_prefixes.append(lit.value)
                    elif name_tail(func.value) == "path" \
                            and lit.value.startswith("/"):
                        self.index.routes.append(RouteSite(
                            route=lit.value, prefix=True,
                            site=_site(self.mi, node)))
            elif func.attr == "_get_json" and node.args:
                self._record_client_path(node.args[0], node)
        self.generic_visit(node)

    def visit_Dict(self, node: ast.Dict):
        # ``{"schema": X, ...}`` writer stamps
        for k, v in zip(node.keys, node.values):
            if isinstance(k, ast.Constant) and k.value == "schema":
                self._record_schema_stamp(v, node)
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare):
        self._record_kind_compare(node)
        self._record_route_compare(node)
        self._record_sse_parse(node)
        self._record_schema_compare(node)
        self.generic_visit(node)

    def visit_JoinedStr(self, node: ast.JoinedStr):
        self._record_request_head(node)
        self.generic_visit(node)

    def visit_Constant(self, node: ast.Constant):
        if isinstance(node.value, str):
            self._record_request_head(node)

    # -- recorders ---------------------------------------------------------

    def _record_metric(self, node: ast.Call, kind: str) -> None:
        families = self.resolver.resolve(node.args[0])
        site = _site(self.mi, node)
        if not families:
            self.index.unresolved_metrics.append(
                (site, ast.unparse(node.args[0])[:60]))
            return
        label_keys: FrozenSet[str] = frozenset()
        opaque = False
        labels = next((kw.value for kw in node.keywords
                       if kw.arg == "labels"), None)
        if labels is not None:
            if isinstance(labels, ast.Dict):
                label_keys, opaque = _dict_literal_keys(labels)
            elif isinstance(labels, ast.Name) \
                    and labels.id in self.resolver.local_dicts:
                label_keys = frozenset(
                    self.resolver.local_dicts[labels.id])
            else:
                opaque = True
        for family in sorted(families):
            self.index.metrics.append(MetricSite(
                family=family, kind=kind, label_keys=label_keys,
                opaque_labels=opaque, site=site))

    def _record_emit(self, node: ast.Call) -> None:
        kinds = self.resolver.resolve(node.args[0])
        site = _site(self.mi, node)
        if not kinds:
            return
        for kind in sorted(kinds):
            self.index.event_emits.setdefault(kind, []).append(site)

    def _record_kind_compare(self, node: ast.Compare) -> None:
        """``e["kind"] == "lit"`` / ``e.get("kind") == "lit"`` /
        ``e["kind"] in ("a", "b")`` — dict-shaped event reads only."""
        def is_kind_read(expr: ast.AST) -> bool:
            if isinstance(expr, ast.Subscript) \
                    and isinstance(expr.slice, ast.Constant):
                return expr.slice.value == "kind"
            if isinstance(expr, ast.Call) \
                    and isinstance(expr.func, ast.Attribute) \
                    and expr.func.attr == "get" and expr.args:
                a0 = expr.args[0]
                return isinstance(a0, ast.Constant) \
                    and a0.value == "kind"
            return False

        sides = [node.left] + list(node.comparators)
        if not any(is_kind_read(s) for s in sides):
            return
        site = _site(self.mi, node)
        for s in sides:
            if isinstance(s, ast.Constant) and isinstance(s.value, str):
                self.index.event_consumers.setdefault(
                    s.value, []).append(site)
            else:
                for v in _const_str_values(s) or ():
                    self.index.event_consumers.setdefault(
                        v, []).append(site)

    def _record_route_compare(self, node: ast.Compare) -> None:
        """``path == "/x"`` / ``path in ("/x", "/")`` route dispatch."""
        if not (isinstance(node.left, ast.Name)
                and node.left.id == "path"):
            return
        site = _site(self.mi, node)
        for comp in node.comparators:
            if isinstance(comp, ast.Constant) \
                    and isinstance(comp.value, str) \
                    and comp.value.startswith("/"):
                self.index.routes.append(RouteSite(
                    route=comp.value, prefix=False, site=site))
            else:
                for v in _const_str_values(comp) or ():
                    if v.startswith("/"):
                        self.index.routes.append(RouteSite(
                            route=v, prefix=False, site=site))

    def _record_sse_parse(self, node: ast.Compare) -> None:
        if not (isinstance(node.left, ast.Name)
                and node.left.id == "event"):
            return
        site = _site(self.mi, node)
        for comp in node.comparators:
            if isinstance(comp, ast.Constant) \
                    and isinstance(comp.value, str):
                self.index.sse_parses.setdefault(
                    comp.value, []).append(site)

    def _record_schema_compare(self, node: ast.Compare) -> None:
        names = set()
        for s in [node.left] + list(node.comparators):
            tail = name_tail(s)
            if tail:
                names.add(tail)
        for sc in self.index.schemas:
            if sc.name in names:
                sc.validated = True
        self._pending_schema_names.update(names)

    def _record_schema_stamp(self, value: ast.AST,
                             node: ast.AST) -> None:
        tail = name_tail(value)
        if tail is not None:
            for sc in self.index.schemas:
                if sc.name == tail:
                    sc.stamped = True
            self._pending_stamp_names.add(tail)
        elif isinstance(value, ast.Constant) \
                and isinstance(value.value, str) \
                and value.value.startswith(_SCHEMA_PREFIX):
            self.index.raw_schema_stamps.append(
                (value.value, _site(self.mi, node)))

    _REQUEST_HEAD = re.compile(
        r"^(?:GET|POST|PUT|DELETE|HEAD) (/[^\s{?]*)")

    def _record_request_head(self, node: ast.AST) -> None:
        """Raw request lines (``f"POST /v1/generate HTTP/1.1..."``):
        the literal path prefix before any query/format field."""
        if isinstance(node, ast.JoinedStr):
            first = node.values[0] if node.values else None
            text = first.value \
                if isinstance(first, ast.Constant) else None
        else:
            text = node.value
        if not isinstance(text, str):
            return
        m = self._REQUEST_HEAD.match(text)
        if m and m.group(1):
            self._record_client_literal(m.group(1),
                                        _site(self.mi, node))

    def _record_client_path(self, arg: ast.AST, node: ast.AST) -> None:
        text: Optional[str] = None
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            text = arg.value
        elif isinstance(arg, ast.JoinedStr) and arg.values \
                and isinstance(arg.values[0], ast.Constant):
            text = str(arg.values[0].value)
        if text and text.startswith("/"):
            self._record_client_literal(text, _site(self.mi, node))

    def _record_client_literal(self, text: str, site: Site) -> None:
        path = text.split("?", 1)[0]
        self.index.client_paths.append((path, site))

    def run(self) -> None:
        self._pending_schema_names: Set[str] = set()
        self._pending_stamp_names: Set[str] = set()
        self._pending_schema_prefixes: List[str] = []
        self.visit(self.mi.tree)


# --------------------------------------------------------------------------
# text surfaces
# --------------------------------------------------------------------------

_BACKTICK = re.compile(r"`([^`]+)`")
_DOC_ROUTE = re.compile(
    r"^(?:GET|POST|PUT|DELETE|HEAD)\s+(/\S*)")
_PROM_TYPE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) (?:counter|gauge|histogram)")
_HEADING = re.compile(r"^#{1,6}\s+(.*)$")


def _table_first_cells(lines: Sequence[str], start: int,
                       path: str) -> List[Tuple[str, Site]]:
    """First-column cells of the markdown table(s) inside one section
    (rows start with ``|``; header + ``---`` separator rows skipped)."""
    out: List[Tuple[str, Site]] = []
    for i in range(start, len(lines)):
        line = lines[i]
        if _HEADING.match(line):
            break
        stripped = line.strip()
        if not stripped.startswith("|"):
            continue
        cells = [c.strip() for c in stripped.strip("|").split("|")]
        if not cells or set(cells[0]) <= {"-", ":", " "}:
            continue
        out.append((cells[0], Site(path=path, line=i + 1)))
    return out


def parse_doc_catalogs(path: str, text: str,
                       index: ContractIndex) -> None:
    """``docs/observability.md``: the "Instrument catalog" and "Event
    catalog" tables. Only the catalog sections count — prose mentions
    of a family elsewhere are narrative, not contract."""
    lines = text.splitlines()
    for i, line in enumerate(lines):
        m = _HEADING.match(line)
        if not m:
            continue
        title = m.group(1).strip().lower()
        if "instrument catalog" in title:
            index.has_doc_metrics = True
            for cell, site in _table_first_cells(lines, i + 1, path):
                for tok in _BACKTICK.findall(cell):
                    if _FAMILY_RE.match(tok):
                        index.doc_metrics.setdefault(tok, site)
        elif "event catalog" in title:
            index.has_doc_events = True
            for cell, site in _table_first_cells(lines, i + 1, path):
                for tok in _BACKTICK.findall(cell):
                    if _EVENT_RE.match(tok):
                        index.doc_events.setdefault(tok, site)


def parse_doc_routes(path: str, text: str,
                     index: ContractIndex) -> None:
    """``docs/http.md``: the endpoint table — ``| `GET /path` | ... |``
    rows. ``<placeholder>`` suffixes and query strings are stripped so
    ``/v1/cancel/<request_id>`` matches the ``startswith`` dispatch."""
    lines = text.splitlines()
    for i, line in enumerate(lines):
        m = _HEADING.match(line)
        if not m or "endpoint" not in m.group(1).strip().lower():
            continue
        index.has_doc_routes = True
        for cell, site in _table_first_cells(lines, i + 1, path):
            for tok in _BACKTICK.findall(cell):
                rm = _DOC_ROUTE.match(tok)
                if not rm:
                    continue
                route = rm.group(1).split("?", 1)[0]
                cut = route.find("<")
                if cut >= 0:
                    route = route[:cut]
                index.doc_routes.setdefault(route, site)


def parse_golden_prom(path: str, text: str,
                      index: ContractIndex) -> None:
    for i, line in enumerate(text.splitlines()):
        m = _PROM_TYPE.match(line)
        if m:
            index.golden_families.setdefault(
                m.group(1), Site(path=path, line=i + 1))


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def build_index(modules: Dict[str, ModuleIndex],
                texts: Dict[str, str]) -> ContractIndex:
    """The whole contract index: python facts from the pre-parsed
    module map, text facts from the doc/golden surface (``texts`` maps
    rel path -> contents for the non-python files)."""
    index = ContractIndex()
    consts = _ModuleConsts(modules)
    extractors = []
    for rel in sorted(modules):
        ex = _ModuleExtractor(modules[rel], consts, index)
        extractors.append(ex)
        ex.run()
    # cross-module schema stamps/validators: a constant defined in one
    # module may be stamped or compared in another (``report.
    # SCENARIOS_SCHEMA`` in scenarios/__main__.py), and module visit
    # order must not matter
    stamp_names: Set[str] = set()
    compare_names: Set[str] = set()
    prefixes: List[str] = []
    for ex in extractors:
        stamp_names |= ex._pending_stamp_names
        compare_names |= ex._pending_schema_names
        prefixes.extend(ex._pending_schema_prefixes)
    for sc in index.schemas:
        if sc.name in stamp_names:
            sc.stamped = True
        if sc.name in compare_names \
                or any(sc.value.startswith(p) for p in prefixes):
            sc.validated = True
    for rel in sorted(texts):
        text = texts[rel]
        base = rel.rsplit("/", 1)[-1]
        if rel.endswith(".prom"):
            parse_golden_prom(rel, text, index)
        elif base == "http.md":
            parse_doc_routes(rel, text, index)
        elif base.endswith(".md"):
            parse_doc_catalogs(rel, text, index)
    return index
