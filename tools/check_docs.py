"""CI docs gate: docs/SCHEDULING.md must document every live schedule.

Fails (exit 1) when a name in the unified registry
(``repro.core.spec.registered_names()``) has no row in the guide's
schedule table, or when the table documents a name the registry no
longer carries — the two drift directions of a hand-written table.

Deliberately importable with numpy alone (``repro.core.spec`` pulls in
no jax), so the CI *lint* job can run it without the full toolchain:

    PYTHONPATH=src python tools/check_docs.py
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
GUIDE = REPO / "docs" / "SCHEDULING.md"

# names the guide documents outside the table by design
NON_REGISTRY_KINDS = {"runtime"}


def documented_names(text: str) -> set:
    """Backticked first-cell names of the guide's schedule table rows: the
    tables of its "Registered schedules" section (other sections' tables,
    such as the serve loop's counters, name no schedules)."""
    section = text.split("\n## Registered schedules", 1)[-1]
    section = section.split("\n## ", 1)[0]
    return set(re.findall(r"(?m)^\|\s*`([a-z0-9_]+)`\s*\|", section))


def main() -> int:
    sys.path.insert(0, str(REPO / "src"))
    from repro.core.spec import registered_names

    live = set(registered_names(source="builtin"))
    if not GUIDE.exists():
        print(f"FAIL: {GUIDE} does not exist")
        return 1
    documented = documented_names(GUIDE.read_text())

    missing = sorted(live - documented)
    stale = sorted(documented - live - NON_REGISTRY_KINDS)
    if missing:
        print(f"FAIL: registered schedules missing from {GUIDE.name}'s "
              f"table: {missing}")
    if stale:
        print(f"FAIL: {GUIDE.name} documents unregistered schedules: "
              f"{stale}")
    if missing or stale:
        return 1
    print(f"OK: {len(live)} registered schedules all documented in "
          f"{GUIDE.name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
