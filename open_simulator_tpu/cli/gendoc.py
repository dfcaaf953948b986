"""gen-doc: argparse tree -> markdown (reference: cmd/doc/generate_markdown.go)."""

from __future__ import annotations

import argparse
import os


def generate_docs(parser: argparse.ArgumentParser, out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    _write_cmd(parser, os.path.join(out_dir, "simon-tpu.md"))
    for action in parser._subparsers._group_actions if parser._subparsers else []:
        if isinstance(action, argparse._SubParsersAction):
            for name, sub in action.choices.items():
                _write_cmd(sub, os.path.join(out_dir, f"simon-tpu_{name}.md"))


def _write_cmd(parser: argparse.ArgumentParser, path: str) -> None:
    lines = [f"## {parser.prog}", "", parser.description or "", "", "```",
             parser.format_help().rstrip(), "```", ""]
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines))
