"""Calls that end in a hang-up or do not: the shape of upstream
`resource/call_hangup.py` as this repo records it
(`avenir_tpu/data/generators.py::generate_call_hangup`), drawn by whole
columns. The hold time is gaussian by the time of day, cut to the field's
range; a call held longer than `threshold` is hung up with probability
`p_long`, a shorter one with `p_short`.

    "generator": {"kind": "hangup_like", "hold": {"AM": [500, 80], "PM": [400, 60]},
                  "threshold": 420, "p_long": 0.8, "p_short": 0.1, ...}
"""

import numpy as np


def draw(rng, n, gen, fields):
    """(codes [n, d], y [n]): for a categorical field the index into its
    `cardinality`, for the `int` field the whole number; y is 1 where the
    caller hung up."""
    codes = np.zeros((n, len(fields)), np.int32)
    hold_at = None
    for j, f in enumerate(fields):
        if f["dataType"] == "categorical":
            codes[:, j] = rng.integers(0, len(f["cardinality"]), n)
        else:
            hold_at = j
    tod_at = next(j for j, f in enumerate(fields)
                  if f["dataType"] == "categorical"
                  and set(f["cardinality"]) == set(gen["hold"]))
    names = fields[tod_at]["cardinality"]
    mean = np.array([gen["hold"][v][0] for v in names], np.float64)
    std = np.array([gen["hold"][v][1] for v in names], np.float64)
    tod = codes[:, tod_at]
    f = fields[hold_at]
    hold = np.clip(rng.normal(mean[tod], std[tod]), f["min"], f["max"] - 1)
    codes[:, hold_at] = hold.astype(np.int32)
    p = np.where(hold > gen["threshold"], gen["p_long"], gen["p_short"])
    return codes, (rng.random(n) < p).astype(np.int8)
