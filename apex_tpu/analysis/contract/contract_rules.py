"""tpu-lint contract rules: producer/consumer drift proofs for the wire.

Every rule here checks one direction of one string-keyed contract
against the shared :class:`~apex_tpu.analysis.contract.extract.
ContractIndex`: instrument families vs the docs catalog and the golden
exposition, event kinds vs their readers, HTTP routes and SSE frames vs
both sides of the socket, and ``apex-tpu/*`` schema pins vs their
writers and validators. The bias matches the other tiers: a rule
speaks only where the index holds a statically resolved fact, and the
repo's intentional gaps are inline-suppressed at the fact's site with a
justification — the baseline ships (and stays) empty.

Rename detection: when a produced family is missing from the docs AND a
near-identical doc entry has no producer, the pair is reported as ONE
``contract-undocumented-metric`` finding naming both sides ("renamed
without updating the catalog?") instead of an undocumented+stale double
hit — drift reports should describe the edit that caused them.
"""

from __future__ import annotations

import dataclasses
import difflib
from typing import Callable, Dict, Iterator, List

from apex_tpu.analysis.contract.extract import (ContractIndex, MetricSite,
                                                Site)
from apex_tpu.analysis.walker import Finding


@dataclasses.dataclass(frozen=True)
class ContractRule:
    name: str
    severity: str
    summary: str
    check: Callable              # check(index: ContractIndex) -> Iterator


CONTRACT_RULES: Dict[str, ContractRule] = {}


def contract_rule(name: str, severity: str, summary: str):
    def deco(fn):
        CONTRACT_RULES[name] = ContractRule(
            name=name, severity=severity, summary=summary, check=fn)
        return fn
    return deco


def _finding(rule: ContractRule, site: Site, message: str) -> Finding:
    return Finding(rule=rule.name, severity=rule.severity,
                   path=site.path, line=site.line, col=site.col,
                   message=message, scope=site.scope,
                   end_line=site.end_line or site.line)


def _first(sites: List[Site]) -> Site:
    return min(sites, key=lambda s: (s.path, s.line, s.col))


def _rename_pairs(index: ContractIndex) -> Dict[str, str]:
    """undocumented-produced-family -> stale-doc-only-family pairs that
    look like a rename (one edit, reported once)."""
    produced = set(index.produced_families())
    undocumented = sorted(produced - set(index.doc_metrics))
    stale = sorted(set(index.doc_metrics) - produced)
    pairs: Dict[str, str] = {}
    taken: set = set()
    for fam in undocumented:
        hit = difflib.get_close_matches(
            fam, [s for s in stale if s not in taken], n=1, cutoff=0.8)
        if hit:
            pairs[fam] = hit[0]
            taken.add(hit[0])
    return pairs


# --------------------------------------------------------------------------
# 1. contract-undocumented-metric
# --------------------------------------------------------------------------

@contract_rule("contract-undocumented-metric", "error",
               "a registered metric family is missing from the docs "
               "instrument catalog (or its name is not statically "
               "resolvable at the registration site)")
def check_undocumented_metric(index: ContractIndex) -> Iterator[Finding]:
    r = CONTRACT_RULES["contract-undocumented-metric"]
    for site, expr in index.unresolved_metrics:
        yield _finding(
            r, site,
            f"metric name `{expr}` is not statically resolvable — the "
            "instrument catalog cannot be checked against it; register "
            "with a literal, a literal tuple loop, or a module "
            "constant")
    if not index.has_doc_metrics:
        return
    pairs = _rename_pairs(index)
    produced = index.produced_families()
    for family in sorted(set(produced) - set(index.doc_metrics)):
        site = _first([m.site for m in produced[family]])
        old = pairs.get(family)
        if old:
            yield _finding(
                r, site,
                f"metric family `{family}` is registered here but the "
                f"docs instrument catalog lists `{old}` — renamed "
                "without updating the catalog?")
        else:
            yield _finding(
                r, site,
                f"metric family `{family}` is registered here but "
                "missing from the docs instrument catalog "
                "(docs/observability.md)")


# --------------------------------------------------------------------------
# 2. contract-stale-doc-metric
# --------------------------------------------------------------------------

@contract_rule("contract-stale-doc-metric", "error",
               "the docs instrument catalog lists a metric family no "
               "code registers")
def check_stale_doc_metric(index: ContractIndex) -> Iterator[Finding]:
    r = CONTRACT_RULES["contract-stale-doc-metric"]
    if not (index.metrics or index.unresolved_metrics):
        return          # no python producer surface scanned at all
    produced = set(index.produced_families())
    renamed_to = set(_rename_pairs(index).values())
    for family in sorted(set(index.doc_metrics) - produced):
        if family in renamed_to:
            continue     # reported once, as the rename, by rule 1
        yield _finding(
            r, index.doc_metrics[family],
            f"instrument catalog lists `{family}` but no code "
            "registers that family")


# --------------------------------------------------------------------------
# 3. contract-label-drift
# --------------------------------------------------------------------------

@contract_rule("contract-label-drift", "error",
               "one metric family is registered with conflicting "
               "label-key sets or conflicting instrument kinds across "
               "sites")
def check_label_drift(index: ContractIndex) -> Iterator[Finding]:
    r = CONTRACT_RULES["contract-label-drift"]
    for family, sites in sorted(index.produced_families().items()):
        by_kind: Dict[str, MetricSite] = {}
        for m in sites:
            by_kind.setdefault(m.kind, m)
        if len(by_kind) > 1:
            kinds = sorted(by_kind)
            second = by_kind[kinds[1]]
            first = by_kind[kinds[0]]
            yield _finding(
                r, second.site,
                f"family `{family}` is registered as a "
                f"{kinds[1]} here but as a {kinds[0]} at "
                f"{first.site.path}:{first.site.line} — one family, "
                "one instrument kind")
        # label-key comparison only between sites whose keys fully
        # resolved; an opaque ``labels=<expr>`` site proves nothing
        seen: Dict[frozenset, MetricSite] = {}
        for m in sites:
            if m.opaque_labels:
                continue
            if m.label_keys not in seen:
                if seen:
                    other = next(iter(seen.values()))
                    yield _finding(
                        r, m.site,
                        f"family `{family}` is registered with label "
                        f"keys {sorted(m.label_keys)} here but "
                        f"{sorted(other.label_keys)} at "
                        f"{other.site.path}:{other.site.line} — "
                        "label sets must agree per family")
                seen[m.label_keys] = m


# --------------------------------------------------------------------------
# 4 / 5. events: orphans and dead consumers
# --------------------------------------------------------------------------

@contract_rule("contract-orphan-event", "error",
               "an emitted event kind has no docs catalog entry and no "
               "code consumer — nobody can be relying on it, or "
               "somebody is and it is invisible")
def check_orphan_event(index: ContractIndex) -> Iterator[Finding]:
    r = CONTRACT_RULES["contract-orphan-event"]
    for kind in sorted(index.event_emits):
        if kind in index.doc_events or kind in index.event_consumers:
            continue
        yield _finding(
            r, _first(index.event_emits[kind]),
            f"event kind `{kind}` is emitted here but appears in no "
            "docs event catalog and no code reads it")


@contract_rule("contract-dead-event-consumer", "error",
               "a docs-cataloged or code-consumed event kind has no "
               "emitter")
def check_dead_event_consumer(index: ContractIndex) -> Iterator[Finding]:
    r = CONTRACT_RULES["contract-dead-event-consumer"]
    for kind in sorted(index.event_consumers):
        if kind in index.event_emits:
            continue
        yield _finding(
            r, _first(index.event_consumers[kind]),
            f"this code filters on event kind `{kind}` but nothing "
            "emits it")
    for kind in sorted(index.doc_events):
        if kind in index.event_emits:
            continue
        yield _finding(
            r, index.doc_events[kind],
            f"docs event catalog lists `{kind}` but nothing emits it")


# --------------------------------------------------------------------------
# 6. contract-schema-unpinned
# --------------------------------------------------------------------------

@contract_rule("contract-schema-unpinned", "error",
               "an apex-tpu/* schema literal lacks its writer stamp or "
               "its paired validator, or a writer stamps a raw string "
               "instead of a named constant")
def check_schema_unpinned(index: ContractIndex) -> Iterator[Finding]:
    r = CONTRACT_RULES["contract-schema-unpinned"]
    for value, site in sorted(index.raw_schema_stamps,
                              key=lambda vs: (vs[1].path, vs[1].line)):
        yield _finding(
            r, site,
            f"writer stamps the raw schema literal `{value}` — promote "
            "it to a named module constant so validators can pin it")
    for sc in sorted(index.schemas, key=lambda s: (s.site.path,
                                                   s.site.line)):
        if not sc.stamped:
            yield _finding(
                r, sc.site,
                f"schema constant `{sc.name}` = \"{sc.value}\" is "
                "never stamped into a written document "
                "(`\"schema\": ...` key)")
        if not sc.validated:
            yield _finding(
                r, sc.site,
                f"schema constant `{sc.name}` = \"{sc.value}\" has no "
                "paired validator (no comparison or prefix check reads "
                "it back)")


# --------------------------------------------------------------------------
# 7. contract-endpoint-undocumented
# --------------------------------------------------------------------------

def _served_by(path: str, index: ContractIndex) -> bool:
    for rt in index.routes:
        if (rt.prefix and path.startswith(rt.route)) \
                or (not rt.prefix and path == rt.route):
            return True
    return False


@contract_rule("contract-endpoint-undocumented", "error",
               "HTTP routes vs the docs endpoint table (both ways), "
               "client request paths vs served routes, and SSE frame "
               "kinds vs the client parsers (both ways)")
def check_endpoint_undocumented(index: ContractIndex) -> Iterator[Finding]:
    r = CONTRACT_RULES["contract-endpoint-undocumented"]
    if index.has_doc_routes:
        reported = set()
        for rt in sorted(index.routes,
                         key=lambda x: (x.route, x.site.path,
                                        x.site.line)):
            if rt.route in reported:
                continue
            documented = rt.route in index.doc_routes or (
                rt.prefix and any(d.startswith(rt.route)
                                  for d in index.doc_routes))
            if not documented:
                reported.add(rt.route)
                yield _finding(
                    r, rt.site,
                    f"route `{rt.route}` is served here but missing "
                    "from the docs endpoint table (docs/http.md)")
        for doc_route in sorted(index.doc_routes):
            if not _served_by(doc_route, index):
                yield _finding(
                    r, index.doc_routes[doc_route],
                    f"docs endpoint table lists `{doc_route}` but no "
                    "dispatch serves it")
    if index.routes:
        for path, site in sorted(index.client_paths,
                                 key=lambda ps: (ps[0], ps[1].path,
                                                 ps[1].line)):
            if not _served_by(path, index):
                yield _finding(
                    r, site,
                    f"client requests `{path}` but no server dispatch "
                    "serves that path")
    parsed = set(index.sse_parses)
    for kind in sorted(index.sse_emits):
        if kind not in parsed:
            yield _finding(
                r, _first(index.sse_emits[kind]),
                f"SSE frame kind `{kind}` is emitted here but no "
                "client parse arm handles it")
    for kind in sorted(parsed - set(index.sse_emits)):
        if index.sse_emits:
            yield _finding(
                r, _first(index.sse_parses[kind]),
                f"client parses SSE frame kind `{kind}` but the "
                "server never emits it")


# --------------------------------------------------------------------------
# 8. contract-golden-stale
# --------------------------------------------------------------------------

_RAW_SERIES_SUFFIXES = ("_count", "_mean", "_last")


@contract_rule("contract-golden-stale", "error",
               "the golden Prometheus exposition pins a family no "
               "registered instrument produces")
def check_golden_stale(index: ContractIndex) -> Iterator[Finding]:
    r = CONTRACT_RULES["contract-golden-stale"]
    if not index.golden_families:
        return
    produced = {f.replace(".", "_") for f in index.produced_families()}
    for fam in sorted(index.golden_families):
        candidates = {fam}
        for suf in _RAW_SERIES_SUFFIXES:
            if fam.endswith(suf):
                candidates.add(fam[: -len(suf)])
        if not candidates & produced:
            yield _finding(
                r, index.golden_families[fam],
                f"golden exposition pins family `{fam}` but no "
                "registered instrument produces it (after the "
                "dots-to-underscores Prometheus mapping)")
