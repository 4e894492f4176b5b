"""The comparison that decides ``correct``: every request retired from the
window's start (the window and the drain after it) against a plain
reference of the same semantics, which imports nothing of the program.

Each kind module under ``kinds/`` holds its own ``check``, which returns
the numbers compared, each with its limit, and its control: the reference
put in the program's place one step below the configuration's stated
guarantee.  The controls are for ``control.py`` and the tests; the
benchmark's own runs never compute them.
"""

from __future__ import annotations

import sys


def check_cell(cell, records: list, missing: int, control: bool = False) -> tuple[dict, int]:
    """Compare ``records`` (the cell's ``Retired`` entries) against the
    reference, with the ``check`` of the kind module that defines the
    cell; ``missing`` counts requests due that never retired."""
    kind = sys.modules[type(cell).__module__]
    return kind.check(cell, records, missing, control)


def passed(checks: dict) -> bool:
    return all(value <= limit for value, limit in checks.values())
