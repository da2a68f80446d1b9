"""tpu-lint command line: discovery, engine, exit codes.

``python -m apex_tpu.analysis`` (or the ``apex-tpu-lint`` console
script) with no path arguments scans the production surface — the
``apex_tpu/`` package plus the repo-root ``tpu_*.py`` drivers. Exit status:

* 0 — clean (every finding suppressed inline or absorbed by the
  baseline);
* 1 — findings above the baseline;
* 2 — usage error / unreadable baseline.

Files that fail to parse produce a ``parse-error`` finding rather than
crashing the run: a syntax error in one driver must not hide findings
in the other twenty files.
"""

from __future__ import annotations

import argparse
import ast
import sys
from pathlib import Path
from typing import Iterable, List, Optional, Sequence, Tuple

from apex_tpu.analysis import report
from apex_tpu.analysis.baseline import Baseline
from apex_tpu.analysis.project import ProjectIndex
from apex_tpu.analysis.rules import RULES, module_rules, project_rules
from apex_tpu.analysis.suppressions import Suppressions
from apex_tpu.analysis.walker import Finding, ModuleIndex

DEFAULT_GLOBS = ("apex_tpu/**/*.py", "tpu_*.py")
DEFAULT_BASELINE = "tpu_lint_baseline.json"

#: generated/vendored files never worth linting
_SKIP_PARTS = {"__pycache__", ".git", ".jax_cache"}


def discover(root: Path, paths: Sequence[str]) -> List[Path]:
    files: List[Path] = []
    if paths:
        for p in paths:
            pp = Path(p)
            if not pp.is_absolute() and not pp.exists() \
                    and (root / pp).exists():
                pp = root / pp       # cwd-relative first, root as fallback
            if pp.is_dir():
                files.extend(sorted(pp.rglob("*.py")))
            else:
                files.append(pp)
    else:
        for pattern in DEFAULT_GLOBS:
            files.extend(sorted(root.glob(pattern)))
    out, seen = [], set()
    for f in files:
        if any(part in _SKIP_PARTS for part in f.parts):
            continue
        key = f.resolve()
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


def _rel(root: Path, path: Path) -> str:
    try:
        return path.resolve().relative_to(root.resolve()).as_posix()
    except ValueError:
        return path.as_posix()


def read_sources(root: Path, paths: Sequence[str] = ()
                 ) -> Tuple["dict[str, str]", List[Finding]]:
    """The discovered surface as ``{rel posix path: source}`` plus
    unreadable-file findings — the one surface reader every source-only
    consumer (AST tier, conc tier, ``--diff``) shares."""
    findings: List[Finding] = []
    sources: "dict[str, str]" = {}
    for path in discover(root, paths):
        rel = _rel(root, path)
        try:
            sources[rel] = path.read_text()
        except OSError as e:
            findings.append(Finding(
                rule="parse-error", severity="error", path=rel, line=1,
                col=1, message=f"unreadable: {e}"))
    return sources, findings


def parse_sources(sources: "dict[str, str]"
                  ) -> Tuple["dict[str, ModuleIndex]", List[Finding]]:
    """Phase 1 for the source-only tiers: parse every module, turning
    syntax errors into findings instead of crashes."""
    findings: List[Finding] = []
    modules: "dict[str, ModuleIndex]" = {}
    for rel in sorted(sources):
        try:
            modules[rel] = ModuleIndex(rel, sources[rel])
        except SyntaxError as e:
            findings.append(Finding(
                rule="parse-error", severity="error", path=rel,
                line=e.lineno or 1, col=(e.offset or 0) + 1,
                message=f"syntax error: {e.msg}"))
    return modules, findings


def analyze_sources(sources: "dict[str, str]", *,
                    select: Optional[Iterable[str]] = None,
                    interprocedural: bool = True,
                    modules: "Optional[dict[str, ModuleIndex]]" = None,
                    ) -> Tuple[List[Finding], int]:
    """Run the MODULE rules over an in-memory ``{rel path: source}``
    map; returns (surviving findings, #suppressed). This is the engine
    under both :func:`analyze_paths` (sources read from disk) and
    ``--diff`` (sources read from a git base rev).

    Phase 1 parses every module; phase 2 (``interprocedural``) links
    them into one call graph (``project.ProjectIndex``) so jit
    reachability and imported jit wrappers cross file boundaries; then
    each module's rules run as before. ``modules`` supplies a
    pre-parsed (and, for interprocedural use, pre-LINKED) map so
    ``--diff`` can feed one parse to both source-only tiers — the
    caller then owns the parse-error findings.
    """
    chosen = set(select) if select is not None else set(RULES)
    findings: List[Finding] = []
    if modules is None:
        modules, findings = parse_sources(sources)
        if interprocedural:
            ProjectIndex(modules).link()
    suppressed = 0
    for rel, mi in modules.items():
        supp = Suppressions(mi.source)
        for r in module_rules():
            if r.name not in chosen:
                continue
            for f in r.check(mi):
                if supp.covers(f):
                    suppressed += 1
                else:
                    findings.append(f)
    return findings, suppressed


def analyze_paths(paths: Sequence[str] = (), *,
                  root: Optional[object] = None,
                  select: Optional[Iterable[str]] = None,
                  with_project_rules: bool = True,
                  ) -> Tuple[List[Finding], int]:
    """Run the rule set; returns (surviving findings, #suppressed).

    ``select`` limits to a subset of rule names (None = all). Inline
    suppressions are already applied; baseline handling is the
    caller's job (`main` does it) so library users see everything.
    """
    root = (Path(root) if root is not None else Path.cwd()).resolve()
    chosen = set(select) if select is not None else set(RULES)
    unknown = chosen - set(RULES)
    if unknown:
        raise ValueError(f"unknown rule(s): {', '.join(sorted(unknown))}")

    sources, findings = read_sources(root, paths)
    module_findings, suppressed = analyze_sources(sources, select=chosen)
    findings.extend(module_findings)
    if with_project_rules:
        for r in project_rules():
            if r.name in chosen:
                findings.extend(r.check(root))
    return findings, suppressed


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="apex-tpu-lint",
        description="AST + jaxpr-IR + host-concurrency + memory-budget "
                    "+ wire-contract static analysis for "
                    "jit/Pallas/serving hazards (five tiers: source, "
                    "staged jaxprs, the host threading/lock/resource "
                    "discipline of the serving stack, per-chip "
                    "HBM/VMEM fit proofs, and producer/consumer drift "
                    "proofs for the string-keyed observability "
                    "surface)")
    p.add_argument("paths", nargs="*",
                   help="files/dirs to scan (default: apex_tpu/, "
                        "tpu_*.py under --root)")
    p.add_argument("--root", default=".",
                   help="repo root for default globs, the baseline file "
                        "and the cross-file drift rules")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--baseline", default=None,
                   help=f"baseline JSON (default: <root>/"
                        f"{DEFAULT_BASELINE} when present)")
    p.add_argument("--write-baseline", action="store_true",
                   help="absorb every current finding into the baseline "
                        "file and exit 0")
    p.add_argument("--show-baselined", action="store_true",
                   help="also print findings the baseline absorbs")
    p.add_argument("--select", default=None,
                   help="comma-separated rule names to run (default all; "
                        "validated against the active tier)")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--ir", action="store_true",
                   help="run the jaxpr IR tier instead of the AST tier: "
                        "trace every registered entry point on CPU "
                        "(no TPU needed) and lint the staged programs")
    p.add_argument("--ir-case", default=None, metavar="NAME",
                   help="IR tier for ONE registered case (implies --ir)")
    p.add_argument("--conc", action="store_true",
                   help="run the host-concurrency tier instead: thread "
                        "coloring, lockset/GuardedBy inference, lock-"
                        "order cycles, blocking-under-lock, resource-"
                        "lifecycle pairing over the whole surface")
    p.add_argument("--mem", action="store_true",
                   help="run the memory-budget tier instead: trace every "
                        "registered case (plus the AOT acceptance "
                        "meshes) on CPU and prove per-chip HBM/VMEM fit "
                        "at tiled-padded sizes, plus shard_map sharding "
                        "contracts")
    p.add_argument("--mem-case", default=None, metavar="NAME",
                   help="mem tier for ONE registered case (implies "
                        "--mem)")
    p.add_argument("--contract", action="store_true",
                   help="run the wire/observability contract tier "
                        "instead: index every metric family, event "
                        "kind, HTTP route, SSE frame and schema pin "
                        "against its consumers (docs "
                        "catalogs, goldens, validators, parsers) and "
                        "prove both directions agree")
    p.add_argument("--diff", default=None, metavar="BASE_REV",
                   help="fail only on findings introduced relative to "
                        "this git rev. Default: AST module rules + the "
                        "conc and contract tiers (source-only, so the "
                        "base rev is analyzable from git history). "
                        "With --mem: the mem tier on both sides — the "
                        "base side runs in a temporary worktree of the "
                        "base rev")
    return p


def _glob_regexes() -> "list":
    """DEFAULT_GLOBS translated to regexes with Path.glob semantics
    (``*`` does not cross ``/``; ``**/`` matches zero or more dirs) —
    fnmatch gets both wrong, and a hand-rolled per-shape matcher would
    silently drop files if the glob list ever grows a new shape."""
    import re

    out = []
    for g in DEFAULT_GLOBS:
        esc = re.escape(g)
        esc = esc.replace(r"\*\*/", "(?:.*/)?").replace(r"\*\*", ".*")
        esc = esc.replace(r"\*", "[^/]*").replace(r"\?", "[^/]")
        out.append(re.compile("^" + esc + "$"))
    return out


def _base_rev_sources(root: Path, rev: str) -> "dict[str, str]":
    """The default lint surface as it existed at ``rev`` (one
    ``git ls-tree`` + one ``git cat-file --batch``); raises ValueError
    on git errors (exit code 2)."""
    import subprocess

    def git(*args: str) -> str:
        proc = subprocess.run(["git", "-C", str(root), *args],
                              capture_output=True, text=True)
        if proc.returncode != 0:
            raise ValueError(
                f"git {' '.join(args[:2])} failed: "
                f"{proc.stderr.strip() or proc.stdout.strip()}")
        return proc.stdout

    regexes = _glob_regexes()

    def on_surface(rel: str) -> bool:
        return any(rx.match(rel) for rx in regexes)

    listing = git("ls-tree", "-r", "--name-only", rev)
    wanted = [rel for rel in listing.splitlines()
              if on_surface(rel)
              and not any(p in _SKIP_PARTS for p in rel.split("/"))]
    if not wanted:
        return {}
    # ONE `cat-file --batch` round trip for all ~130 files (a `git show`
    # per file would pay fork+exec each); bytes mode — the size header
    # counts bytes, not str characters
    proc = subprocess.run(
        ["git", "-C", str(root), "cat-file", "--batch"],
        input="\n".join(f"{rev}:{rel}" for rel in wanted).encode(),
        capture_output=True)
    if proc.returncode != 0:
        raise ValueError(
            f"git cat-file failed: {proc.stderr.decode().strip()}")
    sources: "dict[str, str]" = {}
    buf, pos = proc.stdout, 0
    for rel in wanted:
        nl = buf.index(b"\n", pos)
        header = buf[pos:nl].decode()
        pos = nl + 1
        if header.endswith(("missing", "ambiguous")):
            continue                    # path absent at rev: new file
        size = int(header.rsplit(" ", 1)[1])
        sources[rel] = buf[pos:pos + size].decode(errors="replace")
        pos += size + 1                 # trailing newline after content
    return sources


def _base_rev_texts(root: Path, rev: str) -> "dict[str, str]":
    """The contract tier's text surface (docs catalogs + goldens) as it
    existed at ``rev``. Missing paths are simply absent — a base rev
    that predates a catalog contributes no consumer entries, so
    everything the current catalog pins reads as new."""
    import subprocess

    from apex_tpu.analysis.contract import TEXT_SURFACE

    proc = subprocess.run(
        ["git", "-C", str(root), "cat-file", "--batch"],
        input="\n".join(f"{rev}:{rel}"
                        for rel in TEXT_SURFACE).encode(),
        capture_output=True)
    if proc.returncode != 0:
        raise ValueError(
            f"git cat-file failed: {proc.stderr.decode().strip()}")
    texts: "dict[str, str]" = {}
    buf, pos = proc.stdout, 0
    for rel in TEXT_SURFACE:
        nl = buf.index(b"\n", pos)
        header = buf[pos:nl].decode()
        pos = nl + 1
        if header.endswith(("missing", "ambiguous")):
            continue                    # path absent at rev
        size = int(header.rsplit(" ", 1)[1])
        texts[rel] = buf[pos:pos + size].decode(errors="replace")
        pos += size + 1                 # trailing newline after content
    return texts


def _run_diff(args, root: Path, select) -> int:
    """Diff-aware mode: current module-rule, conc-tier AND
    contract-tier findings, minus whatever the base rev already had
    (counted with the same line-number-free ``path::rule::scope`` keys
    the baseline uses). All three tiers are source-only, so the base
    side is fully analyzable from git history (the contract tier's
    text surface rides along via ``_base_rev_texts``). Project rules
    are skipped on both sides — they need an on-disk tree; the
    absolute gate still runs them."""
    from collections import Counter

    from apex_tpu.analysis.conc.conc_report import (analyze_conc_sources,
                                                    build_model)
    from apex_tpu.analysis.conc.conc_rules import CONC_RULES
    from apex_tpu.analysis.contract import (analyze_contract_sources,
                                            read_text_surface)
    from apex_tpu.analysis.contract.contract_rules import CONTRACT_RULES

    ast_sel = conc_sel = contract_sel = None
    if select is not None:
        ast_sel = [s for s in select if s in RULES]
        conc_sel = [s for s in select if s in CONC_RULES]
        contract_sel = [s for s in select if s in CONTRACT_RULES]

    def all_tiers(sources, texts):
        """AST module rules + conc rules + contract rules over ONE
        parse+link of a surface (each side of the diff pays the parse
        once; the text surface feeds only the contract tier)."""
        model, findings = build_model(sources)
        ast_f, ast_supp = analyze_sources(
            sources, select=ast_sel, modules=model.modules)
        conc_f, conc_supp = analyze_conc_sources(
            sources, select=conc_sel, model=model)
        con_f, con_supp = analyze_contract_sources(
            {**sources, **texts}, select=contract_sel,
            modules=model.modules)
        return (findings + ast_f + conc_f + con_f,
                ast_supp + conc_supp + con_supp)

    try:
        base_sources = _base_rev_sources(root, args.diff)
        base_texts = _base_rev_texts(root, args.diff)
    except ValueError as e:
        print(f"error: --diff {args.diff}: {e}", file=sys.stderr)
        return 2
    base_findings, _ = all_tiers(base_sources, base_texts)
    base = Baseline(Counter(f.baseline_key() for f in base_findings))

    cur_sources, findings = read_sources(root)
    cur_findings, suppressed = all_tiers(cur_sources,
                                         read_text_surface(root))
    findings += cur_findings
    new, absorbed = base.split(findings)
    if args.format == "json":
        print(report.render_json(new, absorbed, suppressed))
    else:
        print(report.render_text(new, absorbed, suppressed,
                                 show_baselined=args.show_baselined))
        if new:
            print(f"tpu-lint: the findings above are NEW relative to "
                  f"{args.diff} ({len(absorbed)} pre-existing "
                  "absorbed)")
    return 1 if new else 0


def _mem_base_findings(root: Path, rev: str) -> "Counter":
    """Baseline-key counts of the mem tier at ``rev``. Unlike the
    source-only tiers, the mem tier TRACES live programs, so a source
    snapshot is not enough — the base rev is materialized in a
    temporary ``git worktree`` and its own ``--mem`` runs there as a
    subprocess (apex_tpu resolves from the working directory, so the
    worktree's code analyzes the worktree's cases). A base rev that
    predates the tier (usage error / unknown flag, exit 2) contributes
    no findings: everything the new tier reports is new."""
    import json as _json
    import os
    import shutil
    import subprocess
    import tempfile
    from collections import Counter

    tmp = Path(tempfile.mkdtemp(prefix="tpu-lint-mem-base-"))
    wt = tmp / "base"
    add = subprocess.run(
        ["git", "-C", str(root), "worktree", "add", "--detach",
         str(wt), rev], capture_output=True, text=True)
    if add.returncode != 0:
        shutil.rmtree(tmp, ignore_errors=True)
        raise ValueError(f"git worktree add {rev} failed: "
                         f"{add.stderr.strip() or add.stdout.strip()}")
    try:
        env = dict(os.environ)
        env["PYTHONPATH"] = str(wt)
        env.setdefault("JAX_PLATFORMS", "cpu")
        # a baseline path that does not exist at the base rev: the diff
        # wants the base's RAW findings, not what its checked-in
        # baseline had already absorbed (render_json reports absorbed
        # findings too, but raw keeps the two sides symmetric)
        proc = subprocess.run(
            [sys.executable, "-m", "apex_tpu.analysis", "--mem",
             "--format", "json",
             "--baseline", str(wt / "_mem_diff_no_baseline.json")],
            cwd=str(wt), env=env, capture_output=True, text=True,
            timeout=1800)
        try:
            data = _json.loads(proc.stdout) \
                if proc.returncode in (0, 1) else None
        except ValueError:
            data = None
        if data is None:
            # exit 2 (pre-mem CLI rejects the flag), a crashed import
            # (the growth seed has no package at all), or junk output:
            # the tier didn't exist there, so nothing can be absorbed
            print(f"tpu-lint: base rev {rev} has no working --mem tier "
                  f"(exit {proc.returncode}); treating every mem "
                  f"finding as new", file=sys.stderr)
            return Counter()
        keys = [f"{f['path']}::{f['rule']}::{f.get('scope', '<module>')}"
                for f in data.get("findings", [])
                + data.get("baselined", [])]
        return Counter(keys)
    finally:
        subprocess.run(["git", "-C", str(root), "worktree", "remove",
                        "--force", str(wt)], capture_output=True)
        shutil.rmtree(tmp, ignore_errors=True)


def _run_mem_diff(args, root: Path, select) -> int:
    """``--diff BASE --mem``: the mem tier on both sides, the base
    side's findings acting as the baseline (same key arithmetic as
    ``_run_diff``). The base side always runs ALL mem rules — a
    --select'ed current side still diffs against the full base so a
    narrowed run cannot misreport pre-existing findings as new."""
    from apex_tpu.analysis.mem import analyze_mem

    base = Baseline(_mem_base_findings(root, args.diff))
    findings, suppressed, _ = analyze_mem(root, select=select)
    new, absorbed = base.split(findings)
    if args.format == "json":
        print(report.render_json(new, absorbed, suppressed))
    else:
        print(report.render_text(new, absorbed, suppressed,
                                 show_baselined=args.show_baselined))
        if new:
            print(f"tpu-lint: the mem findings above are NEW relative "
                  f"to {args.diff} ({len(absorbed)} pre-existing "
                  f"absorbed)")
    return 1 if new else 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = _build_parser().parse_args(argv)
    if args.ir_case:
        args.ir = True
    if args.mem_case:
        args.mem = True
    if args.list_rules:
        from apex_tpu.analysis.conc.conc_rules import CONC_RULES
        from apex_tpu.analysis.contract.contract_rules import \
            CONTRACT_RULES
        from apex_tpu.analysis.ir.ir_rules import IR_RULES
        from apex_tpu.analysis.mem.mem_rules import MEM_RULES

        width = max(len(n) for n in
                    list(RULES) + list(IR_RULES) + list(CONC_RULES)
                    + list(MEM_RULES) + list(CONTRACT_RULES))
        for name, r in sorted(RULES.items()):
            kind = "project" if r.project else "module"
            print(f"{name:<{width}}  {r.severity:<7} ast:{kind:<7} "
                  f"{r.summary}")
        for name, r in sorted(IR_RULES.items()):
            print(f"{name:<{width}}  {r.severity:<7} ir:jaxpr    "
                  f"{r.summary}")
        for name, r in sorted(CONC_RULES.items()):
            print(f"{name:<{width}}  {r.severity:<7} conc:host   "
                  f"{r.summary}")
        for name, r in sorted(MEM_RULES.items()):
            print(f"{name:<{width}}  {r.severity:<7} mem:budget  "
                  f"{r.summary}")
        for name, r in sorted(CONTRACT_RULES.items()):
            print(f"{name:<{width}}  {r.severity:<7} contract:wire "
                  f"{r.summary}")
        return 0

    root = Path(args.root)
    if not root.is_dir():
        print(f"error: --root {root} is not a directory", file=sys.stderr)
        return 2
    select = ([s.strip() for s in args.select.split(",") if s.strip()]
              if args.select else None)
    if sum((args.ir, args.conc, args.mem, args.contract)) > 1:
        print("error: --ir, --conc, --mem and --contract are separate "
              "tiers; run them in separate invocations", file=sys.stderr)
        return 2
    if args.diff is not None:
        if args.ir:
            print("error: --diff covers the source-only tiers (AST "
                  "module rules + conc); the base rev's programs "
                  "cannot be traced from git history — run --ir "
                  "separately", file=sys.stderr)
            return 2
        if args.conc:
            print("error: --diff already covers the conc tier; drop "
                  "--conc", file=sys.stderr)
            return 2
        if args.contract:
            print("error: --diff already covers the contract tier; "
                  "drop --contract", file=sys.stderr)
            return 2
        if args.write_baseline or args.baseline:
            print("error: --diff uses the base rev's findings AS the "
                  "baseline; it neither reads nor writes the baseline "
                  "file (drop --baseline/--write-baseline)",
                  file=sys.stderr)
            return 2
        if args.paths:
            # the base side always lints the default surface; scoping
            # only the current side would misreport an off-surface
            # file's pre-existing findings as new
            print("error: --diff compares the default surface; drop "
                  "the explicit paths", file=sys.stderr)
            return 2
        try:
            if args.mem:
                from apex_tpu.analysis.mem.mem_rules import MEM_RULES

                if select:
                    unknown = set(select) - set(MEM_RULES)
                    if unknown:
                        raise ValueError("unknown mem rule(s): "
                                         + ", ".join(sorted(unknown)))
                return _run_mem_diff(args, root, select)
            if select:
                from apex_tpu.analysis.conc.conc_rules import CONC_RULES
                from apex_tpu.analysis.contract.contract_rules import \
                    CONTRACT_RULES

                unknown = (set(select) - set(RULES) - set(CONC_RULES)
                           - set(CONTRACT_RULES))
                if unknown:
                    raise ValueError("unknown rule(s): "
                                     + ", ".join(sorted(unknown)))
            return _run_diff(args, root, select)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    try:
        if args.ir:
            if args.paths:
                print("error: --ir lints registered entry points, not "
                      "paths (use --ir-case NAME to narrow)",
                      file=sys.stderr)
                return 2
            from apex_tpu.analysis.ir import analyze_ir

            findings, suppressed, _ = analyze_ir(
                root, select=select, case=args.ir_case)
        elif args.conc:
            if args.paths:
                print("error: --conc analyzes the whole default "
                      "surface (locksets and thread colors come from "
                      "the global call graph); drop the explicit paths",
                      file=sys.stderr)
                return 2
            from apex_tpu.analysis.conc import analyze_conc

            findings, suppressed = analyze_conc(root, select=select)
        elif args.contract:
            if args.paths:
                print("error: --contract indexes the whole default "
                      "surface plus the docs/golden text surface (a "
                      "producer and its consumer live in different "
                      "files); drop the explicit paths",
                      file=sys.stderr)
                return 2
            from apex_tpu.analysis.contract import analyze_contract

            findings, suppressed = analyze_contract(root, select=select)
        elif args.mem:
            if args.paths:
                print("error: --mem lints registered entry points, not "
                      "paths (use --mem-case NAME to narrow)",
                      file=sys.stderr)
                return 2
            from apex_tpu.analysis.mem import analyze_mem

            findings, suppressed, _ = analyze_mem(
                root, select=select, case=args.mem_case)
        else:
            findings, suppressed = analyze_paths(
                args.paths, root=root, select=select)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    baseline_path = (Path(args.baseline) if args.baseline
                     else root / DEFAULT_BASELINE)
    if args.write_baseline:
        if select:
            print("error: --write-baseline with --select would record a "
                  "partial view and erase other rules' baselined findings; "
                  "run it unfiltered", file=sys.stderr)
            return 2
        try:
            existing = Baseline.load(baseline_path)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2

        from apex_tpu.analysis.tiers import tier_of_key

        # the tiers share one baseline file but never clobber each
        # other: a write from one tier keeps every other tier's entries
        # (tier membership comes from the rule-namespace registry in
        # analysis/tiers.py, not per-tier string checks)
        active = "ir" if args.ir else "conc" if args.conc \
            else "mem" if args.mem \
            else "contract" if args.contract else "ast"
        keep = {k: v for k, v in existing.counts.items()
                if tier_of_key(k) != active}
        if args.ir and args.ir_case:
            # case-scoped run: replace only THIS case's entries (IR
            # scopes are case names — the last key component)
            keep.update(
                {k: v for k, v in existing.counts.items()
                 if tier_of_key(k) == "ir"
                 and k.split("::")[-1] != args.ir_case})
        elif args.mem and args.mem_case:
            keep.update(
                {k: v for k, v in existing.counts.items()
                 if tier_of_key(k) == "mem"
                 and k.split("::")[-1] != args.mem_case})
        elif active == "ast" and args.paths:
            # scoped run: replace entries for the scanned files
            # only, keep the rest of the baseline untouched
            scanned = {_rel(root, p)
                       for p in discover(root, args.paths)}
            keep.update(
                {k: v for k, v in existing.counts.items()
                 if tier_of_key(k) == "ast"
                 and k.split("::", 1)[0] not in scanned})
        Baseline.write(baseline_path, findings, keep=keep)
        print(f"tpu-lint: wrote {len(findings)} finding(s) to "
              f"{baseline_path}"
              + (f" (kept {sum(keep.values())} out-of-scope)" if keep
                 else ""))
        return 0
    try:
        baseline = Baseline.load(baseline_path)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    new, absorbed = baseline.split(findings)

    if args.format == "json":
        print(report.render_json(new, absorbed, suppressed))
    else:
        print(report.render_text(new, absorbed, suppressed,
                                 show_baselined=args.show_baselined))
    return 1 if new else 0
