#!/usr/bin/env python
"""Markdown link checker (no deps, no network).

Walks the given markdown files/dirs, extracts inline links and checks
that every *relative* target resolves to an existing file (external
http(s) links and bare in-page anchors are skipped — CI has no network).
Also verifies the `file:line` anchors used by docs/ARCHITECTURE.md:
the file part must exist and the line number must be within the file.

CHANGES.md is checked by default (and by the docs CI job) so stale
`file:line` references in the PR log rot loudly instead of silently.

    python tools/check_links.py README.md docs/ CHANGES.md
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
FILE_LINE = re.compile(r"`((?:src|tests|benchmarks|examples|tools|docs)"
                       r"/[\w/.-]+\.(?:py|md|yml)):(\d+)`")


def md_files(args):
    for a in args:
        p = Path(a)
        if p.is_dir():
            yield from sorted(p.rglob("*.md"))
        else:
            yield p


def check(root: Path, files) -> int:
    bad = 0
    for f in files:
        text = f.read_text(encoding="utf-8")
        for m in LINK.finditer(text):
            target = m.group(1)
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            rel = target.split("#", 1)[0]
            if not rel:
                continue
            dest = (f.parent / rel).resolve()
            if not dest.exists():
                print(f"{f}: broken link -> {target}")
                bad += 1
        for m in FILE_LINE.finditer(text):
            path, line = root / m.group(1), int(m.group(2))
            if not path.exists():
                print(f"{f}: anchor file missing -> {m.group(0)}")
                bad += 1
                continue
            n = len(path.read_text(encoding="utf-8").splitlines())
            if line > n:
                print(f"{f}: anchor past EOF ({n} lines) -> {m.group(0)}")
                bad += 1
    return bad


def main() -> int:
    args = sys.argv[1:] or ["README.md", "docs", "CHANGES.md"]
    root = Path.cwd()
    files = list(md_files(args))
    bad = check(root, files)
    print(f"[check_links] {len(files)} files, "
          f"{'OK' if not bad else f'{bad} broken'}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
