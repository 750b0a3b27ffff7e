"""Tiny structured logger (stdout, rank-0 aware)."""

from __future__ import annotations

import json
import sys
import time
from typing import Any

_T0 = time.time()


def log(event: str, **fields: Any) -> None:
    rec = {"t": round(time.time() - _T0, 3), "event": event}
    rec.update(fields)
    try:
        sys.stdout.write(json.dumps(rec, default=str) + "\n")
    except TypeError:
        sys.stdout.write(str(rec) + "\n")
    sys.stdout.flush()

