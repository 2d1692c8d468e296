"""The comparison that decides ``correct``: the numbers a run computes from
what its timed path produced and from the plain reference, and their limits
(``checks/<workload>.json``)."""
from __future__ import annotations

import json
import math


def load_limits(path) -> dict:
    """{number: limit} from a cell's checks file (its readings stay in the
    file beside each limit, for the reader)."""
    return {k: float(v["limit"])
            for k, v in json.loads(open(path).read())["numbers"].items()}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct when every number is finite and
    within its limit, and every limit has its number."""
    table = {k: {"value": numbers.get(k, math.nan), "limit": lim}
             for k, lim in limits.items()}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
