"""paddle_tpu.analysis — graftlint, the repo's AST-based invariant
checker (ISSUE 6).  Turns CLAUDE.md's hard-won architecture rules into
enforced static checks; see docs/ANALYSIS.md for the rule catalog and
``python tools/lint.py --help`` for the CLI.

jax-free on purpose: ``tools/lint.py`` imports this package through a
stub parent module so linting never touches jax (a lint run must not
claim the chip or pay a backend start-up).  Nothing under
``paddle_tpu.analysis`` may import jax or sibling subpackages.
"""
from __future__ import annotations

from .core import (BAD_BASELINE, BAD_SUPPRESSION, FileContext, Finding,
                   Project, Rule, apply_baseline, load_baseline,
                   run_paths, run_source, save_baseline)
from .rules import ALL_RULES, RULES_BY_ID
from . import knobs

__all__ = [
    "ALL_RULES", "RULES_BY_ID", "BAD_BASELINE", "BAD_SUPPRESSION",
    "FileContext", "Finding", "Project", "Rule", "apply_baseline",
    "knobs", "load_baseline", "run_paths", "run_source",
    "save_baseline",
]
