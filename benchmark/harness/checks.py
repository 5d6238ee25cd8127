"""Numbers compared for ``correct``, each beside its limit."""
from __future__ import annotations

import math
import sys


class Checks:
    def __init__(self, limits: dict):
        self.limits = limits
        self.rows: dict[str, dict] = {}
        self.not_compared: dict[str, float] = {}

    def add(self, name: str, value: float):
        """Compare ``value`` with the limit its configuration or mix gives;
        a number with no limit is printed as not compared."""
        value = float(value)
        if name not in self.limits:
            self.not_compared[name] = value
            return
        limit = float(self.limits[name])
        ok = math.isfinite(value) and value <= limit
        self.rows[name] = {"value": value, "limit": limit, "ok": ok}

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows.values())

    def print(self):
        for name, r in self.rows.items():
            print(f"check {name}: value {r['value']!r} limit {r['limit']!r} "
                  f"{'ok' if r['ok'] else 'NOT OK'}", file=sys.stderr, flush=True)
