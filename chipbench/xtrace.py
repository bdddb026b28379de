"""From the profiler's `.xplane.pb` to plain event lists: the device's
operations and programs, and the host's annotations, all on the
profiler's clock in nanoseconds."""

from __future__ import annotations

import glob
import os
from typing import Dict, List

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def newest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"the profiler wrote no trace under {log_dir}")
    return max(files, key=os.path.getmtime)


def read_events(path: str, annotation_prefix: str = "chipbench.") -> Dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}},
    "annotations": [...], "lines": {plane: [line names]}} of one trace.
    An event is [name, start_ns, duration_ns]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict = {"devices": {}, "annotations": [], "lines": {}}
    for plane in data.planes:
        name = plane.name
        lines = list(plane.lines)
        out["lines"][name] = [ln.name for ln in lines]
        if name.startswith("/device:") and not name.startswith("/device:CPU"):
            dev = {"ops": [], "modules": []}
            for ln in lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(ln.name)
                if key is None:
                    continue
                dev[key] = [[e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in ln.events]
            if dev["ops"] or dev["modules"]:
                out["devices"][name] = dev
        elif name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    if e.name.startswith(annotation_prefix):
                        out["annotations"].append(
                            [e.name, float(e.start_ns), float(e.duration_ns)])
    return out


def window_of(annotations: List, name: str):
    """(start_ns, end_ns) of the one annotation called `name`."""
    hits = [a for a in annotations if a[0] == name]
    if len(hits) != 1:
        raise ValueError(f"want one annotation {name!r}, found {len(hits)}")
    return hits[0][1], hits[0][1] + hits[0][2]
