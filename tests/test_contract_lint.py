"""tpu-lint contract tier (apex_tpu.analysis.contract) coverage.

Mirrors the PR 7 load-bearing pattern for the fifth tier, per ISSUE 20:

1. per-rule fixture pairs — a bad surface (python + text files) that
   triggers EXACTLY its rule (and passes with the rule deselected), and
   a good twin that is clean;
2. machinery — rename pairing, raw-stamp detection, inline suppression
   in BOTH pragma dialects (tokenize for ``.py``, line-regex for the
   markdown/prom surface), the tier-partitioned baseline, CLI usage
   errors, ``--diff`` coverage, the golden regeneration helper;
3. seeded mutations against the LIVE repo: renaming one ``fleet.*``
   gauge, dropping one SSE frame kind from the client parsers, and
   stripping a schema pin each light exactly one rule;
4. end-to-end — ``--contract`` over the repo itself exits 0 at HEAD:
   the tier-1 twin of the ``--contract`` CI gate.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from apex_tpu.analysis import cli                              # noqa: E402
from apex_tpu.analysis.contract import (CONTRACT_RULES,        # noqa: E402
                                        analyze_contract_sources,
                                        build_contract_index,
                                        read_text_surface)
from apex_tpu.analysis.tiers import tier_of, tier_of_key       # noqa: E402

# --------------------------------------------------------------------------
# per-rule fixture pairs: {rule: (bad surface, good surface)} where a
# surface is a {rel path: content} map mixing python and text files
# --------------------------------------------------------------------------

_CATALOG_ONE = """\
## Instrument catalog

| family | meaning |
| --- | --- |
| `serving.base` | documented |
"""

_CATALOG_BOTH = _CATALOG_ONE + "| `serving.fresh` | documented too |\n"

_CATALOG_STALE = _CATALOG_ONE + "| `serving.gone_stat` | retired |\n"

_TWO_FAMILIES = """\
def observe(metrics):
    metrics.counter("serving.base").inc()
    metrics.counter("serving.fresh").inc()
"""

_ENDPOINTS_ONE = """\
## Endpoints

| route | notes |
| --- | --- |
| `GET /ok` | fine |
"""

_ENDPOINTS_BOTH = _ENDPOINTS_ONE + "| `GET /zap` | also served |\n"

_DISPATCH = """\
def dispatch(path):
    if path == "/ok":
        return 1
    if path == "/zap":
        return 2
    return 0
"""

_GOLDEN_OK = """\
# HELP serving_ok requests admitted
# TYPE serving_ok counter
serving_ok 3
"""

_GOLDEN_STALE = _GOLDEN_OK + """\
# TYPE serving_gone counter
serving_gone 1
"""

FIXTURES = {
    "contract-undocumented-metric": (
        {"apex_tpu/mod.py": _TWO_FAMILIES,
         "docs/observability.md": _CATALOG_ONE},
        {"apex_tpu/mod.py": _TWO_FAMILIES,
         "docs/observability.md": _CATALOG_BOTH},
    ),
    "contract-stale-doc-metric": (
        {"apex_tpu/mod.py": _TWO_FAMILIES,
         "docs/observability.md": _CATALOG_STALE.replace(
             "| `serving.base` | documented |\n",
             "| `serving.base` | documented |\n"
             "| `serving.fresh` | documented too |\n")},
        {"apex_tpu/mod.py": _TWO_FAMILIES,
         "docs/observability.md": _CATALOG_BOTH},
    ),
    "contract-label-drift": (
        {"apex_tpu/mod.py": """\
def one(metrics, shard):
    metrics.counter("pool.allocs", labels={"shard": shard}).inc()

def two(metrics, tier):
    metrics.counter("pool.allocs", labels={"tier": tier}).inc()
"""},
        {"apex_tpu/mod.py": """\
def one(metrics, shard):
    metrics.counter("pool.allocs", labels={"shard": shard}).inc()

def two(metrics, shard):
    metrics.counter("pool.allocs", labels={"shard": shard}).inc()
"""},
    ),
    "contract-orphan-event": (
        {"apex_tpu/mod.py": """\
def run(events):
    events.emit("zap", {"n": 1})
"""},
        {"apex_tpu/mod.py": """\
def run(events):
    events.emit("zap", {"n": 1})

def react(e):
    if e["kind"] == "zap":
        return 1
    return 0
"""},
    ),
    "contract-dead-event-consumer": (
        {"apex_tpu/mod.py": """\
def react(e):
    if e["kind"] == "ghost":
        return 1
    return 0
"""},
        {"apex_tpu/mod.py": """\
def run(events):
    events.emit("ghost", {"n": 1})

def react(e):
    if e["kind"] == "ghost":
        return 1
    return 0
"""},
    ),
    "contract-schema-unpinned": (
        {"apex_tpu/mod.py": """\
DOC_SCHEMA = "apex-tpu/thing/v1"
"""},
        {"apex_tpu/mod.py": """\
DOC_SCHEMA = "apex-tpu/thing/v1"

def write(payload):
    return {"schema": DOC_SCHEMA, "payload": payload}

def validate(doc):
    if doc.get("schema") != DOC_SCHEMA:
        raise ValueError("bad schema")
    return doc
"""},
    ),
    "contract-endpoint-undocumented": (
        {"apex_tpu/mod.py": _DISPATCH,
         "docs/http.md": _ENDPOINTS_ONE},
        {"apex_tpu/mod.py": _DISPATCH,
         "docs/http.md": _ENDPOINTS_BOTH},
    ),
    "contract-golden-stale": (
        {"apex_tpu/mod.py": """\
def observe(metrics):
    metrics.counter("serving.ok").inc()
""",
         "tests/golden/observability.prom": _GOLDEN_STALE},
        {"apex_tpu/mod.py": """\
def observe(metrics):
    metrics.counter("serving.ok").inc()
""",
         "tests/golden/observability.prom": _GOLDEN_OK},
    ),
}


def _run(sources, select=None):
    return analyze_contract_sources(dict(sources), select=select)


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_bad_surface_triggers_exactly_its_rule(rule):
    findings, _ = _run(FIXTURES[rule][0])
    fired = [f.rule for f in findings]
    assert fired, f"bad surface for {rule} produced no findings"
    assert set(fired) == {rule}, fired


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_good_surface_is_clean(rule):
    findings, _ = _run(FIXTURES[rule][1])
    assert not findings, [(f.rule, f.message) for f in findings]


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_contract_rules_individually_load_bearing(rule):
    """With the rule deselected (≈ deleted), its bad surface passes: no
    other contract rule shadows it."""
    others = [r for r in CONTRACT_RULES if r != rule]
    findings, _ = _run(FIXTURES[rule][0], select=others)
    assert not findings, [(f.rule, f.message) for f in findings]


def test_every_contract_rule_has_a_fixture():
    assert set(CONTRACT_RULES) == set(FIXTURES)


# --------------------------------------------------------------------------
# machinery: rename pairing, raw stamps, suppression, tiers, CLI
# --------------------------------------------------------------------------

def test_rename_reported_once_naming_both_sides():
    """A produced family missing from the docs paired with a
    near-identical doc-only family is ONE undocumented-metric finding
    describing the rename, not an undocumented + stale double hit."""
    sources = {
        "apex_tpu/mod.py": """\
def observe(metrics):
    metrics.counter("serving.retired_total").inc()
""",
        "docs/observability.md": """\
## Instrument catalog

| family | meaning |
| --- | --- |
| `serving.retire_total` | old name |
""",
    }
    findings, _ = _run(sources)
    assert [f.rule for f in findings] == ["contract-undocumented-metric"]
    msg = findings[0].message
    assert "renamed" in msg
    assert "serving.retired_total" in msg
    assert "serving.retire_total" in msg


def test_unresolvable_metric_name_is_reported():
    findings, _ = _run({"apex_tpu/mod.py": """\
def observe(metrics, name):
    metrics.counter(name).inc()
"""})
    assert [f.rule for f in findings] == ["contract-undocumented-metric"]
    assert "not statically resolvable" in findings[0].message


def test_raw_schema_stamp_is_reported():
    findings, _ = _run({"apex_tpu/mod.py": """\
def write(payload):
    return {"schema": "apex-tpu/raw/v1", "payload": payload}
"""})
    assert [f.rule for f in findings] == ["contract-schema-unpinned"]
    assert "raw schema literal" in findings[0].message


def test_client_path_must_be_served():
    """The client side of the route contract: a request path no server
    dispatch serves fires even when the docs table is absent."""
    findings, _ = _run({"apex_tpu/mod.py": _DISPATCH + """\

def probe(client):
    return client._get_json("/nope")
"""})
    assert [f.rule for f in findings] == \
        ["contract-endpoint-undocumented"]
    assert "/nope" in findings[0].message


def test_sse_contract_both_directions():
    src = """\
class Srv:
    async def _sse(self, writer, kind, payload):
        return kind

    async def serve(self, writer):
        await self._sse(writer, "token", {})
        await self._sse(writer, "done", {})

def parse(event):
    if event == "token":
        return 1
    if event == "ghost":
        return 2
    return 0
"""
    findings, _ = _run({"apex_tpu/mod.py": src})
    msgs = {f.message for f in findings}
    assert {f.rule for f in findings} == \
        {"contract-endpoint-undocumented"}
    assert any("`done`" in m for m in msgs)      # emitted, never parsed
    assert any("`ghost`" in m for m in msgs)     # parsed, never emitted


def test_contract_finding_is_inline_suppressible_in_python():
    bad = FIXTURES["contract-schema-unpinned"][0]["apex_tpu/mod.py"]
    src = bad.replace(
        'DOC_SCHEMA = "apex-tpu/thing/v1"',
        'DOC_SCHEMA = "apex-tpu/thing/v1"  '
        "# tpu-lint: disable=contract-schema-unpinned -- test")
    findings, suppressed = _run({"apex_tpu/mod.py": src})
    assert not findings
    assert suppressed == 2           # unstamped + unvalidated, one site


def test_contract_finding_is_inline_suppressible_in_markdown():
    """The text-surface pragma dialect: an HTML comment on the line
    above a table row suppresses findings anchored to that row."""
    bad = dict(FIXTURES["contract-stale-doc-metric"][0])
    bad["docs/observability.md"] = bad["docs/observability.md"].replace(
        "| `serving.gone_stat` | retired |",
        "<!-- tpu-lint: disable=contract-stale-doc-metric -- kept -->\n"
        "| `serving.gone_stat` | retired |")
    findings, suppressed = _run(bad)
    assert not findings, [(f.rule, f.message) for f in findings]
    assert suppressed == 1


def test_tier_registry_covers_contract():
    assert tier_of("contract-golden-stale") == "contract"
    assert tier_of("conc-lock-order-cycle") == "conc"
    assert tier_of_key("a.py::contract-orphan-event::fn") == "contract"
    assert tier_of_key("a.py::host-sync-in-jit::fn") == "ast"


def test_contract_write_baseline_keeps_other_tiers(tmp_path, monkeypatch):
    """--contract --write-baseline replaces only contract-* entries;
    AST, IR and conc debt survives."""
    from apex_tpu.analysis.walker import Finding

    baseline = tmp_path / "tpu_lint_baseline.json"
    baseline.write_text(json.dumps({"version": 1, "findings": {
        "x.py::contract-orphan-event::old": 1,
        "y.py::ir-dead-output::case_b": 2,
        "z.py::conc-resource-leak::fn": 3,
    }}))
    fresh = Finding(rule="contract-golden-stale", severity="error",
                    path="g.prom", line=1, col=1, message="m",
                    scope="<module>")
    import apex_tpu.analysis.contract as contract_pkg
    monkeypatch.setattr(contract_pkg, "analyze_contract",
                        lambda root, select=None: ([fresh], 0))
    assert cli.main(["--root", str(tmp_path), "--contract",
                     "--write-baseline"]) == 0
    counts = json.loads(baseline.read_text())["findings"]
    assert counts == {
        "g.prom::contract-golden-stale::<module>": 1,  # tier replaced
        "y.py::ir-dead-output::case_b": 2,             # IR kept
        "z.py::conc-resource-leak::fn": 3,             # conc kept
    }


def test_contract_cli_usage_errors(capsys):
    assert cli.main(["--root", REPO, "--contract",
                     "--select", "no-such-contract-rule"]) == 2
    # conc rule names are not valid in contract mode
    assert cli.main(["--root", REPO, "--contract",
                     "--select", "conc-lock-order-cycle"]) == 2
    assert cli.main(["apex_tpu", "--root", REPO, "--contract"]) == 2
    assert cli.main(["--root", REPO, "--contract", "--mem"]) == 2
    assert cli.main(["--root", REPO, "--contract",
                     "--diff", "HEAD"]) == 2


def test_list_rules_shows_contract_tier(capsys):
    assert cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "contract:wire" in out
    assert "contract-golden-stale" in out
    assert "mem:budget" in out


# --------------------------------------------------------------------------
# the golden regeneration helper and its contract-tier check
# --------------------------------------------------------------------------

def test_golden_regeneration_matches_checked_in_file(tmp_path):
    """``python -m apex_tpu.obs.export --golden`` reproduces the
    checked-in golden byte-for-byte — the seed registry in export.py is
    the single source both the test and the regeneration share."""
    from apex_tpu.obs import export
    from apex_tpu.utils import metrics

    # seed_golden_registry() writes the process-wide registry; clear on
    # both sides so the golden families (different histogram params)
    # never collide with later tests' production registrations
    metrics.clear()
    try:
        out = tmp_path / "observability.prom"
        assert export.main(["--golden", "--out", str(out)]) == 0
        checked_in = Path(REPO, "tests", "golden",
                          "observability.prom").read_text()
        assert out.read_text() == checked_in
    finally:
        metrics.clear()


def test_golden_families_are_produced_at_head():
    """Every ``# TYPE`` family the golden pins maps back (dots to
    underscores, raw-series suffixes stripped) to a family some live
    registration site produces — what contract-golden-stale proves."""
    index, parse_findings = build_contract_index(_contract_sources())
    assert not parse_findings
    assert index.golden_families, "golden exposition lost its TYPE lines"
    produced = {f.replace(".", "_") for f in index.produced_families()}
    for fam in index.golden_families:
        candidates = {fam}
        for suf in ("_count", "_mean", "_last"):
            if fam.endswith(suf):
                candidates.add(fam[: -len(suf)])
        assert candidates & produced, fam


# --------------------------------------------------------------------------
# --diff covers the contract tier
# --------------------------------------------------------------------------

_DIFF_PY = """\
def observe(metrics):
    metrics.counter("scratch.ok").inc()
"""

_DIFF_DOC = """\
## Instrument catalog

| family | meaning |
| --- | --- |
| `scratch.ok` | fine |
"""


def _git(repo, *args):
    subprocess.run(["git", "-C", str(repo), *args], check=True,
                   capture_output=True,
                   env={**os.environ,
                        "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                        "GIT_COMMITTER_NAME": "t",
                        "GIT_COMMITTER_EMAIL": "t@t"})


def test_diff_covers_contract_tier(tmp_path, capsys):
    """A metric family registered since the base rev without a catalog
    entry fails the diff gate; the committed state is diff-clean."""
    _git(tmp_path, "init", "-q")
    mod = tmp_path / "tpu_scratch.py"
    mod.write_text(_DIFF_PY)
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "observability.md").write_text(_DIFF_DOC)
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "base")
    assert cli.main(["--root", str(tmp_path), "--diff", "HEAD"]) == 0
    capsys.readouterr()
    mod.write_text(_DIFF_PY + """\

def observe_more(metrics):
    metrics.counter("scratch.fresh").inc()
""")
    rc = cli.main(["--root", str(tmp_path), "--diff", "HEAD"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "contract-undocumented-metric" in out
    assert "scratch.fresh" in out


# --------------------------------------------------------------------------
# seeded mutations against the live repo surface
# --------------------------------------------------------------------------

def _surface_sources():
    root = Path(REPO)
    return {cli._rel(root, p): p.read_text()
            for p in cli.discover(root, ())}


def _contract_sources():
    sources = _surface_sources()
    sources.update(read_text_surface(REPO))
    return sources


_FLEET = "apex_tpu/obs/fleet.py"
_FLEET_GAUGE = 'metrics.gauge("fleet.scrape_age_s"'


def test_mutation_renamed_gauge_is_caught():
    """ISSUE 20 acceptance: renaming one ``fleet.*`` gauge at its live
    registration site fires exactly contract-undocumented-metric, as a
    rename pairing naming both the new and the cataloged name."""
    sources = _contract_sources()
    src = sources[_FLEET]
    assert src.count(_FLEET_GAUGE) == 1, "fleet gauge anchor moved"
    sources[_FLEET] = src.replace(
        _FLEET_GAUGE, 'metrics.gauge("fleet.scrape_age_z"')
    findings, _ = analyze_contract_sources(sources)
    assert {f.rule for f in findings} == \
        {"contract-undocumented-metric"}, \
        [(f.rule, f.message) for f in findings]
    msg = findings[0].message
    assert "fleet.scrape_age_z" in msg
    assert "fleet.scrape_age_s" in msg


_SSE_DONE = 'elif event == "done":'
_SSE_CONSUMERS = ("apex_tpu/serving/http.py",
                  "apex_tpu/serving/scenarios/http_driver.py")


def test_mutation_dropped_sse_parse_arm_is_caught():
    """ISSUE 20 acceptance: dropping the ``done`` parse arm from EVERY
    live SSE client (parse facts union across files) fires exactly
    contract-endpoint-undocumented on the emit site."""
    sources = _contract_sources()
    for rel in _SSE_CONSUMERS:
        assert sources[rel].count(_SSE_DONE) == 1, \
            f"SSE done-arm anchor moved in {rel}"
        sources[rel] = sources[rel].replace(
            _SSE_DONE, 'elif event == "token":')
    findings, _ = analyze_contract_sources(sources)
    assert {f.rule for f in findings} == \
        {"contract-endpoint-undocumented"}, \
        [(f.rule, f.message) for f in findings]
    assert any("`done`" in f.message for f in findings)


_REPORT = "apex_tpu/serving/scenarios/report.py"
_SCHEMA_STAMP = '        "schema": REPORT_SCHEMA,\n'


def test_mutation_stripped_schema_pin_is_caught():
    """ISSUE 20 acceptance: removing the report writer's schema stamp
    fires exactly contract-schema-unpinned on the constant."""
    sources = _contract_sources()
    src = sources[_REPORT]
    assert src.count(_SCHEMA_STAMP) == 1, "report schema stamp moved"
    sources[_REPORT] = src.replace(_SCHEMA_STAMP, "")
    findings, _ = analyze_contract_sources(sources)
    assert {f.rule for f in findings} == {"contract-schema-unpinned"}, \
        [(f.rule, f.message) for f in findings]
    assert "REPORT_SCHEMA" in findings[0].message
    assert "never stamped" in findings[0].message


def test_unmutated_surface_is_clean():
    """The live surface carries no contract findings beyond the
    inline-suppressed intentional gaps."""
    findings, suppressed = analyze_contract_sources(_contract_sources())
    assert not findings, [(f.rule, f.path, f.line) for f in findings]
    assert suppressed >= 1           # the documented intentional gaps


# --------------------------------------------------------------------------
# end-to-end: the repo is contract-clean at HEAD (tier-1 gate twin)
# --------------------------------------------------------------------------

def test_repo_contract_is_clean_at_head(capsys):
    rc = cli.main(["--root", REPO, "--contract"])
    out = capsys.readouterr().out
    assert rc == 0, \
        f"tpu-lint --contract found new issues in the repo:\n{out}"
