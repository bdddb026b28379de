"""The parts of the comparison that decides `correct` which every kind of
check shares: the sample drawn from the seed, the bytes that differ
between two outputs, and the verdict, number by number against the limits
the configuration's file states. What is compared, and with what
reference, is the check the configuration names: `checks/<kind>.py`.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import generate

SAMPLE_STREAM = 1 << 20              # seed stream of the sample's draw


def sample_picks(seed: int, lengths: Sequence[int], sample_rows: int
                 ) -> List[np.ndarray]:
    """The rows compared, drawn from the seed: of each job's test file
    (`lengths` rows each) the same number, `sample_rows` in all, as sorted
    row numbers."""
    rng = generate.seed_for(seed, SAMPLE_STREAM)
    per_job = max(1, sample_rows // len(lengths))
    return [np.sort(rng.choice(n, size=min(per_job, n), replace=False))
            for n in lengths]


def unstable_bytes(a: bytes, b: bytes) -> int:
    m = min(len(a), len(b))
    diff = int(np.count_nonzero(
        np.frombuffer(a[:m], np.uint8) != np.frombuffer(b[:m], np.uint8)))
    return diff + abs(len(a) - len(b))


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, List[Dict]]:
    """(`correct`, one row per number compared: name, value, limit, ok).
    A number the limits name and the run did not produce fails."""
    rows, good = [], True
    for name, limit in limits.items():
        value: Optional[float] = numbers.get(name)
        ok = bool(value is not None and np.isfinite(value)
                  and value <= limit)
        good = good and ok
        rows.append({"name": name, "value": value, "limit": limit, "ok": ok})
    return good, rows
