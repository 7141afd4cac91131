#!/usr/bin/env python
"""Documentation gate for `make ci`.

Checks, in order:

1. required docs exist (README.md, docs/architecture.md,
   docs/serving_vision.md);
2. every relative markdown link in README.md and docs/*.md resolves to a
   real file (anchors and external URLs are skipped);
3. the README layout table names every package under src/repro/ —
   the acceptance invariant that the map cannot silently rot as the repo
   grows;
4. the README quickstart commands run in dry-run form: python entry
   points with --help (imports + argparse wiring must work), make targets
   with -n (recipes must exist).

Exit code 0 = all green; every failure is listed before exiting 1.
"""
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REQUIRED_DOCS = [
    "README.md",
    os.path.join("docs", "architecture.md"),
    os.path.join("docs", "serving_vision.md"),
]

# README quickstart, dry-run form: --help proves import + argparse wiring
# without paying model compiles; make -n proves the target exists.
QUICKSTART_HELP = [
    [sys.executable, "-m", "repro.launch.serve_vision", "--help"],
    [sys.executable, "-m", "benchmarks.run", "--help"],
    [sys.executable, os.path.join("examples", "serve_vision.py"), "--help"],
    [sys.executable, "chip_smoke.py", "--help"],
]
QUICKSTART_MAKE = ["test", "test-fast", "bench-smoke", "restart-check",
                   "multiprocess-check", "docs-check", "ci"]

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")


def md_files():
    out = [os.path.join(ROOT, "README.md")]
    docs = os.path.join(ROOT, "docs")
    for name in sorted(os.listdir(docs)):
        if name.endswith(".md"):
            out.append(os.path.join(docs, name))
    return out


def check_links(errors):
    for path in md_files():
        with open(path) as f:
            text = f.read()
        # drop fenced code blocks: their brackets aren't links
        text = re.sub(r"```.*?```", "", text, flags=re.S)
        for target in LINK_RE.findall(text):
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            target = target.split("#")[0]
            if not target:
                continue
            resolved = os.path.normpath(
                os.path.join(os.path.dirname(path), target))
            if not os.path.exists(resolved):
                errors.append(f"{os.path.relpath(path, ROOT)}: broken link "
                              f"-> {target}")


def check_layout_table(errors):
    with open(os.path.join(ROOT, "README.md")) as f:
        readme = f.read()
    pkg_root = os.path.join(ROOT, "src", "repro")
    for name in sorted(os.listdir(pkg_root)):
        full = os.path.join(pkg_root, name)
        if not os.path.isdir(full):
            continue
        if not any(fn.endswith(".py") for fn in os.listdir(full)):
            continue
        if f"src/repro/{name}" not in readme:
            errors.append(f"README.md layout table is missing package "
                          f"src/repro/{name}")


def check_quickstart(errors):
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    for cmd in QUICKSTART_HELP:
        proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                              text=True, timeout=180)
        if proc.returncode != 0:
            errors.append(f"quickstart dry-run failed: {' '.join(cmd)}\n"
                          f"  {proc.stderr.strip().splitlines()[-1] if proc.stderr.strip() else '(no stderr)'}")
    for target in QUICKSTART_MAKE:
        proc = subprocess.run(["make", "-n", target], cwd=ROOT,
                              capture_output=True, text=True, timeout=60)
        if proc.returncode != 0:
            errors.append(f"quickstart make target missing: make {target}")


def main() -> int:
    errors = []
    for rel in REQUIRED_DOCS:
        if not os.path.exists(os.path.join(ROOT, rel)):
            errors.append(f"required doc missing: {rel}")
    if not errors:                      # later checks read these files
        check_links(errors)
        check_layout_table(errors)
        check_quickstart(errors)
    if errors:
        print("docs-check: FAILED")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(f"docs-check: OK ({len(md_files())} markdown files, links + "
          f"layout table + quickstart dry-runs)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
