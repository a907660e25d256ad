#!/usr/bin/env python
"""Docs reference check: the architecture docs must cite only real things.

Two passes over README.md, DESIGN.md, and every ``docs/*.md``:

1. **File references** — file-like tokens (anything ending in a code
   extension) must resolve somewhere in the repo. Bare names and
   package-relative paths are resolved against a small set of candidate
   roots (repo root, src/repro, docs, benchmarks, examples, tests, tools),
   matching how the docs abbreviate paths (`train/elastic.py` ==
   `src/repro/train/elastic.py`). A bare ``BENCH_*.json`` resolves to its
   pinned copy in benchmarks/baselines/ (the root copy is written at run
   time). Paths under generated directories (results/) are exempt: they
   legitimately do not exist in a fresh checkout.
2. **Symbol references** (``docs/*.md`` only — the deep guides that rot
   fastest) — every backtick-quoted Python-identifier-looking token
   (``build_halo_plan``, ``HaloPlan``, ``repro.dist.halo`` …) must appear
   somewhere in the source tree (src/, bench/, benchmarks/, examples/,
   tests/, tools/) or name one of its modules, each dotted component
   checked as a whole word. A renamed or deleted symbol fails CI instead of
   silently rotting the guide.

    python tools/check_docs_refs.py
"""
from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
ARCH_DOCS = ("README.md", "DESIGN.md")
GENERATED = ("results/",)
CANDIDATE_ROOTS = (
    "", "src/repro", "docs", "benchmarks", "benchmarks/baselines", "examples", "tests", "tools",
)
SOURCE_DIRS = ("src", "bench", "benchmarks", "examples", "tests", "tools")
TOKEN = re.compile(r"[\w.\-/]+\.(?:py|md|yml|yaml|toml|txt|json)\b")
# `code`-quoted tokens that look like Python identifiers or dotted paths
# (pure identifier chars, starting with a letter/underscore, no slashes).
SYMBOL = re.compile(r"`([A-Za-z_][A-Za-z0-9_]*(?:\.[A-Za-z_][A-Za-z0-9_]*)*)`")


def docs() -> list[pathlib.Path]:
    return [ROOT / d for d in ARCH_DOCS] + sorted((ROOT / "docs").glob("*.md"))


def resolves(token: str) -> bool:
    while token.startswith("./"):
        token = token[2:]
    for root in CANDIDATE_ROOTS:
        if (ROOT / root / token).exists():
            return True
    return False


def source_text() -> str:
    chunks = []
    for d in SOURCE_DIRS:
        for path in sorted((ROOT / d).rglob("*.py")):
            chunks.append(".".join(path.relative_to(ROOT).with_suffix("").parts))
            chunks.append(path.read_text(encoding="utf-8"))
    return "\n".join(chunks)


def missing_symbols(text: str, src: str) -> list[str]:
    out = []
    for tok in sorted({m.group(1) for m in SYMBOL.finditer(text)}):
        parts = tok.split(".")
        if len(parts) == 1 and len(tok) <= 2:
            continue  # single letters / `k` / `d` math shorthand
        if all(re.search(rf"\b{re.escape(p)}\b", src) for p in parts):
            continue
        out.append(tok)
    return out


def main() -> int:
    failures: list[str] = []
    src = source_text()
    for path in docs():
        doc = path.relative_to(ROOT).as_posix()
        text = path.read_text(encoding="utf-8")
        for tok in sorted({m.group(0) for m in TOKEN.finditer(text)}):
            if tok.startswith(GENERATED):
                continue
            if not resolves(tok):
                failures.append(f"MISSING FILE: {doc} references {tok!r} which does not exist")
        if doc.startswith("docs/"):
            for tok in missing_symbols(text, src):
                failures.append(
                    f"MISSING SYMBOL: {doc} references `{tok}` which appears nowhere in "
                    f"{'/'.join(SOURCE_DIRS)}"
                )
    if failures:
        print("\n".join(failures))
        return 1
    print(f"docs refs OK ({', '.join(p.relative_to(ROOT).as_posix() for p in docs())})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
