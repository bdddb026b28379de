"""Two classes of rows, each at a level of its own on every field: the
shape of the upstream tutorial's `resource/elearn.py` as this repo
records it (`tests/test_reference_configs.py::_elearn_rows`). A row is of
class 1 with probability one half; each of its fields is the class's
level plus gaussian noise, as a share of the field's `max`, cut to the
field's [min, max] and to a whole number of the field's unit (an `int`
field's unit is 1, a `double`'s a thousandth).

    "generator": {"kind": "two_level", "levels": [0.3, 0.7], "sigma": 0.12, ...}
"""

import numpy as np

_UNITS = {"int": 1, "long": 1, "double": 1000, "float": 1000}


def draw(rng, n, gen, fields):
    """(q [n, d] whole numbers of each field's unit, y [n] class codes)."""
    levels = np.asarray(gen["levels"], np.float64)
    if levels.shape != (2,):
        raise ValueError("generator.levels wants two numbers")
    lo = np.array([f["min"] for f in fields], np.float64)
    hi = np.array([f["max"] for f in fields], np.float64)
    unit = np.array([_UNITS[f["dataType"]] for f in fields], np.float64)
    y = (rng.random(n) < 0.5).astype(np.int8)
    x = levels[y][:, None] + rng.normal(0.0, float(gen["sigma"]),
                                        (n, len(fields)))
    x *= hi
    np.clip(x, lo, hi, out=x)
    x *= unit
    return x.astype(np.int32), y        # cut, as int() cuts
