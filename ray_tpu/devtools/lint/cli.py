"""`python -m ray_tpu.devtools.lint` — the tpulint CLI.

Exit codes: 0 = clean (every finding baselined), 1 = new findings (or
requested strictness violated), 2 = usage/config error.

Config comes from ``[tool.tpulint]`` in pyproject.toml (found by walking up
from the first target path): ``paths``, ``baseline``, ``checks``,
``exclude``. CLI flags override config.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

from . import baseline as baseline_mod
from .checks import run_checks
from .discovery import discover
from .engine import analyze
from .model import CHECKS


def _parse_toml_section(path: str, section: str) -> dict:
    """Minimal TOML reader for our own flat section (py3.10: no tomllib).

    Supports `key = "str"`, `key = true/false`, and (multi-line) string
    arrays — exactly the shapes [tool.tpulint] uses.
    """
    try:
        with open(path, encoding="utf-8") as f:
            src = f.read()
    except OSError:
        return {}
    m = re.search(rf"^\[{re.escape(section)}\]\s*$(.*?)(?=^\[|\Z)", src, re.M | re.S)
    if not m:
        return {}
    body = m.group(1)
    out: dict = {}
    # join multi-line arrays
    body = re.sub(r"\[\s*\n", "[", body)
    while re.search(r"\[[^\]]*\n", body):
        body = re.sub(r"(\[[^\]]*)\n\s*", r"\1 ", body, count=1)
    for line in body.splitlines():
        line = line.split("#", 1)[0].strip()
        if not line or "=" not in line:
            continue
        key, _, val = line.partition("=")
        key, val = key.strip(), val.strip()
        if val.startswith("["):
            out[key] = re.findall(r"\"([^\"]*)\"|'([^']*)'", val)
            out[key] = [a or b for a, b in out[key]]
        elif val in ("true", "false"):
            out[key] = val == "true"
        else:
            out[key] = val.strip("\"'")
    return out


def _changed_files(repo_root: str) -> list | None:
    """Absolute paths of .py files differing from `git merge-base HEAD main`
    plus uncommitted/untracked ones; None when git can't answer (no repo, no
    main — the caller falls back to a full run)."""
    import subprocess

    def _git(*argv):
        proc = subprocess.run(
            ["git", *argv],
            cwd=repo_root,
            capture_output=True,
            text=True,
            timeout=30,
        )
        if proc.returncode != 0:
            raise RuntimeError(proc.stderr.strip())
        return proc.stdout

    try:
        base = _git("merge-base", "HEAD", "main").strip()
        names = set(_git("diff", "--name-only", base, "--", "*.py").splitlines())
        # working-tree edits and untracked files ride along
        names |= set(_git("diff", "--name-only", "--", "*.py").splitlines())
        for line in _git("status", "--porcelain").splitlines():
            p = line[3:].strip()
            if " -> " in p:  # rename entry: lint the new path
                p = p.split(" -> ", 1)[1]
            if p.startswith('"') and p.endswith('"'):
                p = p[1:-1]
            if p.endswith(".py"):
                names.add(p)
    except (RuntimeError, OSError, subprocess.TimeoutExpired):
        return None
    out = []
    for n in sorted(names):
        ap = os.path.join(repo_root, n)
        if os.path.exists(ap):
            out.append(os.path.abspath(ap))
    return out


def _find_pyproject(start: str) -> str | None:
    d = os.path.abspath(start)
    if os.path.isfile(d):
        d = os.path.dirname(d)
    for _ in range(10):
        cand = os.path.join(d, "pyproject.toml")
        if os.path.exists(cand):
            return cand
        parent = os.path.dirname(d)
        if parent == d:
            return None
        d = parent
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m ray_tpu.devtools.lint",
        description=(
            "tpulint: concurrency + SPMD + resource-lifecycle + wire-"
            "protocol static analysis for ray_tpu (lock-order, "
            "blocking-under-lock, async-stall, unguarded-shared-state, "
            "shutdown-hygiene, collective-uniformity, ref-lifecycle, "
            "wire-conformance)"
        ),
    )
    ap.add_argument("paths", nargs="*", help="files/trees to lint (default: config paths, else the ray_tpu package)")
    ap.add_argument("--baseline", help="baseline JSON path ('' disables)")
    ap.add_argument("--no-baseline", action="store_true", help="ignore any baseline: report every finding as new")
    ap.add_argument("--write-baseline", action="store_true", help="accept current findings into the baseline (reasons preserved by fingerprint)")
    ap.add_argument("--checks", help="comma-separated check ids to run (default: all)")
    ap.add_argument(
        "--write-protocol-doc",
        action="store_true",
        help=(
            "regenerate the wire-protocol document (default docs/PROTOCOL.md, "
            "config key protocol_doc) from the extracted op catalog and exit; "
            "full-tree lint runs fail when the checked-in doc has drifted"
        ),
    )
    ap.add_argument(
        "--changed-only",
        action="store_true",
        help=(
            "lint only files that differ from `git merge-base HEAD main` "
            "(plus uncommitted changes), sharing the full-tree baseline — "
            "the <1s inner-loop mode; the full-tree run remains the gate"
        ),
    )
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--list-checks", action="store_true")
    ap.add_argument("--stats", action="store_true", help="print index/analysis counters")
    args = ap.parse_args(argv)

    if args.list_checks:
        for name, desc in CHECKS.items():
            print(f"{name}\n    {desc}")
        return 0

    # ---- config ----------------------------------------------------------
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    repo_root = os.path.dirname(pkg_root)
    seed = args.paths[0] if args.paths else repo_root
    pyproject = _find_pyproject(seed)
    cfg = _parse_toml_section(pyproject, "tool.tpulint") if pyproject else {}
    cfg_root = os.path.dirname(pyproject) if pyproject else repo_root

    paths = args.paths or [
        os.path.join(cfg_root, p) for p in cfg.get("paths", [])
    ] or [pkg_root]
    for p in paths:
        if not os.path.exists(p):
            print(f"tpulint: no such path: {p}", file=sys.stderr)
            return 2

    if args.write_protocol_doc and (args.paths or args.changed_only):
        # a slice sees only part of the handler/send surface — writing the
        # doc from it would silently drop every out-of-slice op (and a
        # clean --changed-only run would otherwise exit 0 without writing)
        print(
            "tpulint: --write-protocol-doc requires a full-tree run "
            "(drop --changed-only/path args)",
            file=sys.stderr,
        )
        return 2

    if args.write_baseline and (
        args.changed_only or (args.paths and args.baseline is None)
    ):
        # baseline.write rebuilds the file from THIS run's findings: a
        # slice would silently delete every out-of-slice entry from the
        # shared full-tree baseline (reviewed reasons included). Refused
        # before the slice is computed: a clean tree has no changed files
        # and would otherwise exit 0 here.
        print(
            "tpulint: --write-baseline requires a full-tree run "
            "(a slice would truncate the shared baseline); drop "
            "--changed-only/path args, or pass an explicit --baseline "
            "file for a standalone slice baseline",
            file=sys.stderr,
        )
        return 2

    changed_slice = False
    if args.changed_only:
        changed = _changed_files(cfg_root)
        if changed is None:
            print(
                "tpulint: --changed-only: git diff unavailable, "
                "falling back to a full run",
                file=sys.stderr,
            )
        else:
            roots = [os.path.abspath(p) for p in paths]
            picked = [
                f
                for f in changed
                if any(f == r or f.startswith(r + os.sep) for r in roots)
            ]
            if not picked:
                print("tpulint: --changed-only: no changed files under the lint paths; clean")
                return 0
            paths = picked
            changed_slice = True

    enabled = None
    if args.checks:
        enabled = [c.strip() for c in args.checks.split(",") if c.strip()]
    elif cfg.get("checks"):
        enabled = cfg["checks"]
    if enabled:
        unknown = set(enabled) - set(CHECKS)
        if unknown:
            print(f"tpulint: unknown checks: {sorted(unknown)}", file=sys.stderr)
            return 2

    if args.baseline is not None:
        baseline_path = args.baseline or None
    else:
        rel = cfg.get("baseline", os.path.join("tools", "tpulint_baseline.json"))
        baseline_path = os.path.join(cfg_root, rel)

    # ---- run --------------------------------------------------------------
    t0 = time.monotonic()
    # changed-only slices report relative to the config root so fingerprints
    # line up with the (full-tree) baseline
    project = discover(paths, root=cfg_root if changed_slice else None)
    project.config = cfg
    # wire-conformance runs its protocol-doc drift check on full runs only
    # (a slice's partial catalog would always "drift")
    project.full_tree = not args.paths and not changed_slice
    doc_rel = cfg.get("protocol_doc", os.path.join("docs", "PROTOCOL.md"))
    cfg.setdefault("protocol_doc", doc_rel)
    analyze(project)

    if args.write_protocol_doc:
        from .wire import write_protocol_doc

        doc_path = (
            doc_rel if os.path.isabs(doc_rel) else os.path.join(cfg_root, doc_rel)
        )
        write_protocol_doc(project, doc_path)
        print(f"tpulint: wrote protocol doc to {doc_path}")
        return 0

    findings = run_checks(project, enabled)
    # config-level excludes (path prefixes relative to the report root)
    for pat in cfg.get("exclude", []):
        findings = [f for f in findings if not f.file.startswith(pat)]
    elapsed = time.monotonic() - t0

    base = {} if (args.no_baseline or not baseline_path) else baseline_mod.load(baseline_path)
    new, accepted, stale = baseline_mod.split(findings, base)
    # Stale entries gate FULL runs only: a leftover fingerprint would
    # silently re-accept the same bug if it were ever reintroduced, so the
    # baseline must shrink when findings are fixed. On an explicit path
    # slice (including --changed-only) most of the baseline is legitimately
    # unmatched — report, don't fail.
    full_run = not args.paths and not changed_slice

    if args.write_baseline:
        if not baseline_path:
            print("tpulint: --write-baseline needs a baseline path", file=sys.stderr)
            return 2
        baseline_mod.write(baseline_path, findings, old=base)
        print(
            f"tpulint: wrote {len(findings)} findings to {baseline_path} "
            f"({len(new)} newly accepted)"
        )
        return 0

    if args.format == "json":
        print(
            json.dumps(
                {
                    "new": [vars(f) | {"fingerprint": f.fingerprint} for f in new],
                    "accepted": len(accepted),
                    "stale_baseline": [e["fingerprint"] for e in stale],
                    "elapsed_s": round(elapsed, 2),
                },
                indent=1,
                default=str,
            )
        )
    else:
        for f in new:
            print(f.render())
        if stale:
            print(
                f"\ntpulint: {len(stale)} stale baseline entr"
                f"{'y' if len(stale) == 1 else 'ies'} (finding fixed — delete "
                f"from {baseline_path}"
                + ("; stale entries FAIL full runs" if full_run else "")
                + "):"
            )
            for e in stale:
                print(f"    {e['fingerprint']}  {e['file']}  [{e['check']}] {e['qualname']}")
        summary = (
            f"tpulint: {len(new)} new, {len(accepted)} baselined, "
            f"{len(stale)} stale baseline entries; "
            f"{len(project.modules)} modules in {elapsed:.1f}s"
        )
        print(("\n" if new else "") + summary)
        if args.stats:
            cat = getattr(project, "_wire_catalog", None)
            if cat is not None and cat.dead_ops:
                print(
                    f"tpulint: wire: {len(cat.dead_ops)} handler op(s) with "
                    f"no in-tree sender (report-only): "
                    f"{', '.join(cat.dead_ops)}"
                )
            nfuncs = len(project.functions)
            nlocks = len(getattr(project, "locks", {}))
            nblocks = sum(len(f.block_sites) for f in project.functions.values())
            print(
                f"tpulint: stats: {nfuncs} functions, {nlocks} locks, "
                f"{nblocks} blocking sites, {len(project.errors)} parse errors"
            )
            for file, msg in project.errors:
                print(f"    {file}: {msg}")

    return 1 if new or (stale and full_run) else 0


if __name__ == "__main__":
    sys.exit(main())
