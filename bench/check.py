"""The comparison that decides ``correct``: served records against the
plain reference (``reference.py``), field by field.

A record matches when ``ok``, ``dist``, ``k_used``, ``cigar``,
``read_consumed``, ``ref_consumed`` and the op array all equal the
reference's for that pair.  The numbers compared, each with its limit:

* ``mismatched``: compared records that differ from the reference (0);
* ``unanswered``: requests whose answer never came, or came as an error
  other than a shed (0);
* ``compared``: records compared (at least ``min_compared``), so a run
  that compared nothing is not correct.
"""
from __future__ import annotations

import numpy as np

from . import reference

FIELDS = ("ok", "dist", "k_used", "cigar", "read_consumed", "ref_consumed")


def geometry(config: dict, rescue_rounds: int | None = None):
    a, s = config["aligner"], config["session"]
    return reference.Geometry(
        W=int(a["W"]), O=int(a["O"]), k=int(a["k"]),
        rescue_rounds=int(s["rescue_rounds"] if rescue_rounds is None
                          else rescue_rounds))


def record_differs(got: dict, want: dict) -> bool:
    return (any(got[f] != want[f] for f in FIELDS)
            or not np.array_equal(np.asarray(got["ops"]), want["ops"]))


def sample(requested, n_check: int, rng) -> list[int]:
    """The pool entries whose answers are compared: a seeded sample of the
    entries the window requested (all of them when they are few)."""
    requested = list(requested)
    if n_check >= len(requested):
        return requested
    return sorted(int(i) for i in rng.choice(requested, size=n_check,
                                             replace=False))


def compare(kept, unanswered: int, picked, want_by_entry,
            min_compared=1) -> dict:
    """kept: [(pool_index, record)] for every answer of a picked entry;
    unanswered: requests whose answer never came, or came as an error
    other than a shed.  want_by_entry: {pool_index: reference record} for
    the picked entries.  Returns the checks {name: {value, limit, rule}}."""
    mismatched = 0
    for idx, rec in kept:
        mismatched += record_differs(rec, want_by_entry[idx])
    return {
        "mismatched": {"value": mismatched, "limit": 0, "rule": "<="},
        "unanswered": {"value": int(unanswered), "limit": 0, "rule": "<="},
        "compared": {"value": len(kept), "limit": min_compared,
                     "rule": ">="},
    }


def passed(checks: dict) -> bool:
    ok = True
    for c in checks.values():
        if c["rule"] == "<=":
            ok &= c["value"] <= c["limit"]
        else:
            ok &= c["value"] >= c["limit"]
    return bool(ok)


def reference_records(pool, picked, config: dict, geo=None) -> dict:
    """{pool_index: reference record} for the picked entries."""
    geo = geo or geometry(config)
    idx = [int(i) for i in picked]
    recs = reference.align([pool[i][0] for i in idx],
                           [pool[i][1] for i in idx], geo)
    return dict(zip(idx, recs))
