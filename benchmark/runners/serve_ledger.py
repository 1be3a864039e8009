"""Kept for its name (PR 53; the program's docs/telemetry.md and ``tools/cell_journal.py``
speak of the ``serve_ledger`` runner): since PR 56 the plain ``serve`` runner reads the
program's host ledger itself, for every serving cell, and this module is that runner and
its ``ledger_observations`` under the old name. A cell may name either."""

from benchmark.runners.serve import ROWS, Runner, ledger_observations  # noqa: F401
