"""Calls to a service line that end in a hang-up or do not: upstream
`resource/call_hangup.py` as this repo records it
(`avenir_tpu/data/generators.py::generate_call_hangup`), drawn by whole
columns. A caller is a business with probability `p_business`; its issue
is one of `issues.business` or of `issues.residence`, each alike; the call
comes in the morning or the afternoon alike; the hold time is gaussian by
the time of day, cut to the field's range less one and to a whole number;
a call held longer than `threshold` is hung up with probability `p_long`,
a shorter one with `p_short`. The columns the schema does not declare, and
the id, are drawn by `draw_unread`: no computation reads them.

    "generator": {"kind": "call_hangup", "p_business": 0.4,
                  "issues": {"business": [...], "residence": [...]},
                  "hold": {"AM": [500, 80], "PM": [400, 60]},
                  "threshold": 420, "p_long": 0.8, "p_short": 0.1,
                  "unread": {"2": [408, 607, 336, 646, 206]}, "id_digits": 10, ...}
"""

import numpy as np


def _column(fields, name):
    return next(j for j, f in enumerate(fields) if f["name"] == name)


def draw(rng, n, gen, fields):
    """(codes [n, d] int16, y [n] int8) over the feature fields, by
    ordinal: a categorical field's index into its `cardinality`, the
    `int` field's number; y is 1 where the caller hung up."""
    cust_at, issue_at = _column(fields, "customer type"), _column(fields, "issue")
    tod_at, hold_at = _column(fields, "time of day"), _column(fields, "hold time")
    codes = np.empty((n, len(fields)), np.int16)
    kinds = fields[cust_at]["cardinality"]
    business = rng.random(n) < gen["p_business"]
    codes[:, cust_at] = np.where(business, kinds.index("business"),
                                 kinds.index("residence"))
    issues = fields[issue_at]["cardinality"]
    of = {k: np.array([issues.index(v) for v in gen["issues"][k]], np.int16)
          for k in ("business", "residence")}
    pick = rng.random(n)
    codes[:, issue_at] = np.where(
        business,
        of["business"][(pick * len(of["business"])).astype(np.int64)],
        of["residence"][(pick * len(of["residence"])).astype(np.int64)])
    times = fields[tod_at]["cardinality"]
    tod = (rng.random(n) < 0.5).astype(np.int16)
    codes[:, tod_at] = tod
    mean = np.array([gen["hold"][v][0] for v in times], np.float64)
    std = np.array([gen["hold"][v][1] for v in times], np.float64)
    f = fields[hold_at]
    hold = np.clip(rng.normal(mean[tod], std[tod]), f["min"], f["max"] - 1)
    codes[:, hold_at] = hold.astype(np.int16)        # cut, as int() cuts
    p = np.where(hold > gen["threshold"], gen["p_long"], gen["p_short"])
    return codes, (rng.random(n) < p).astype(np.int8)


def draw_unread(rng, n, gen):
    """(ids [n] int64 of `id_digits` digits, {ordinal: index into the
    column's values [n]}) for the columns no computation reads."""
    digits = int(gen["id_digits"])
    ids = rng.integers(10 ** (digits - 1), 10 ** digits, n)
    return ids, {int(o): rng.integers(0, len(vals), n).astype(np.int8)
                 for o, vals in sorted(gen["unread"].items())}
