"""The check of a frequent-itemset job (`frequentItemsApriori`): the
directory of `itemsets-k.txt` files each timed job wrote against the plain
reference (`fia_reference.py`) over all baskets. Found by `reference.kind`
in the configuration's file. Imports nothing of the program.

  sets_bad         jobs whose output is not a set of itemset files: none,
                   a length missing below the longest, a surplus file, a
                   line that does not parse or names an item the file does
                   not hold, a line out of order or twice, items unsorted
  unstable_bytes   bytes that differ between the warm-up job's files and
                   the window's first job's (same input file)
  support_wrong    reported sets, all of them at every length, whose
                   printed support is not the reference's exact count over
                   n to the sixth decimal
  sets_surplus     reported sets whose exact count is not over the
                   threshold
  closure_broken   reported sets with a subset one shorter that is not
                   reported
  sets_missing     sets whose exact count is over the threshold and which
                   are not reported, among: every item; at lengths 2 and 3
                   every subset of the generator's own patterns (those
                   with a subset that cannot be frequent left out), and a
                   sample drawn from the seed, `SAMPLE_SETS` a length, of
                   the candidates (the reference's join and prune of what
                   the job reported one length below) the job did not
                   report
  support_gap_max  the largest distance between a printed support and the
                   reference's, printed alike: 0.0 where both print the
                   float64 quotient of the same integers; the control's
                   supports, kept in bfloat16, stand apart by their eighth
                   bit

Every job of the window reads the same file, so an output whose bytes equal
one already compared is not compared again.
"""

from __future__ import annotations

import itertools
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import compare, generate
from chipbench import fia_reference as ref

#: the numbers of which the lower-precision control has to fail one
CONTROL_FAILS = ("support_gap_max", "support_wrong")
SAMPLE_SETS = 2048                   # unreported candidates tried, a length
SAMPLE_STREAM = 1 << 21
EXACT = ("sets_bad", "unstable_bytes", "support_wrong", "sets_surplus",
         "closure_broken", "sets_missing")
Levels = Dict[int, List[Tuple[ref.ItemSet, str]]]


def sizes(cell, inputs) -> Dict:
    """The semantic sizes of the traced job: baskets, items, the frequent
    items V', and each later round's candidates: all pairs of frequent
    items at length 2, the reference's join and prune of the reported
    sets one length below after that."""
    sem = ref.job_semantics(cell.config["properties"])
    seen = getattr(inputs, "seen", None) or {"frequent": 0, "candidates": {}}
    return {"n": inputs.n, "items": inputs.columns.n_items,
            "frequent": seen["frequent"], "max_length": sem["max_length"],
            "candidates": dict(seen["candidates"])}


def read_files(folder: str) -> bytes:
    """The output's files in name order, each after its name, as one blob
    (what `unstable_bytes` compares); empty where there is none."""
    if not os.path.isdir(folder):
        return b""
    parts = []
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name), "rb") as fh:
            parts.append(name.encode() + b"\n" + fh.read())
    return b"\n".join(parts)


def parse_files(folder: str, max_length: int, item_of: Dict[str, int]
                ) -> Levels:
    """{k: [(items, printed support), ...]} of one job's output; raises
    ValueError, KeyError or OSError on whatever `sets_bad` counts."""
    names = sorted(os.listdir(folder))
    if not names or len(names) > max_length or names != [
            f"itemsets-{k}.txt" for k in range(1, len(names) + 1)]:
        raise ValueError(f"the files are {names}")
    levels: Levels = {}
    for k, name in enumerate(names, start=1):
        with open(os.path.join(folder, name)) as fh:
            rows = [ln.rstrip("\n").split(",") for ln in fh]
        if not rows:
            raise ValueError(f"{name} is empty")
        sets = []
        for row in rows:
            if len(row) != k + 1 or len(row[k].split(".")[-1]) != 6:
                raise ValueError(f"{name}: {row}")
            float(row[k])
            if sorted(set(row[:k])) != row[:k]:
                raise ValueError(f"{name}: items unsorted or twice: {row}")
            sets.append((row[:k], row[k]))
        if any(a[0] >= b[0] for a, b in zip(sets, sets[1:])):
            raise ValueError(f"{name}: a line out of order or twice")
        levels[k] = [(tuple(item_of[t] for t in toks), said)
                     for toks, said in sets]
    return levels


def pattern_subsets(pats: Dict, k: int) -> List[ref.ItemSet]:
    """Every subset of k items of every pattern, once, ascending."""
    out = set()
    for row, size in zip(pats["items"], pats["size"]):
        out.update(itertools.combinations(sorted(int(i) for i in row[:size]), k))
    return sorted(out)


def compare_levels(levels: Levels, cols: ref.Columns, pats: Dict, sem: Dict,
                   seed: int) -> Dict:
    """One output's numbers against the baskets, and what `sizes` hands
    the roofline (`frequent`, `candidates`)."""
    n, threshold = cols.n, sem["threshold"]
    out = {"support_wrong": 0, "sets_surplus": 0, "closure_broken": 0,
           "sets_missing": 0, "support_gap_max": 0.0, "sets": 0,
           "candidates": {}}
    frequent_item = cols.item_counts() > threshold * n
    reported = {k: {s for s, _said in sets} for k, sets in levels.items()}
    for k, sets in levels.items():
        counts = cols.counts([s for s, _said in sets])
        for (s, said), c in zip(sets, counts):
            want = f"{c / n:.6f}"
            out["support_wrong"] += said != want
            out["support_gap_max"] = max(out["support_gap_max"],
                                         abs(float(said) - float(want)))
            out["sets_surplus"] += not ref.over(c, threshold, n)
            out["closure_broken"] += k > 1 and any(
                s[:j] + s[j + 1:] not in reported[k - 1] for j in range(k))
        out["sets"] += len(sets)
    out["frequent"] = int(frequent_item.sum())
    out["sets_missing"] += sum(
        1 for i in np.flatnonzero(frequent_item)
        if (int(i),) not in reported.get(1, ()))
    rng = generate.seed_for(seed, SAMPLE_STREAM)
    for k in range(2, min(sem["max_length"], 3) + 1):
        have = reported.get(k, set())
        cands = ref.join_and_prune(reported.get(k - 1, ()), k)
        out["candidates"][k] = len(cands)
        spare = [c for c in cands if c not in have]
        pick = rng.choice(len(spare), min(SAMPLE_SETS, len(spare)),
                          replace=False) if spare else []
        tried = {spare[i] for i in pick}
        # a set with a subset that is not frequent is not frequent either:
        # an item by its exact count; one length up, a subset the job did
        # not report (were it frequent, it counts as missing itself)
        below = reported.get(k - 1, set()) if k > 2 else None
        tried.update(
            s for s in pattern_subsets(pats, k)
            if s not in have and all(frequent_item[list(s)])
            and (below is None or all(s[:j] + s[j + 1:] in below
                                      for j in range(k))))
        tried = sorted(tried)
        out["sets_missing"] += sum(
            ref.over(c, threshold, n) for c in cols.counts(tried))
    return out


def numbers(cell, inputs, seed, jobs, warm_out) -> Dict:
    sem = ref.job_semantics(cell.config["properties"])
    item_of = {t: i for i, t in enumerate(inputs.tokens)}
    out = {name: 0 for name in EXACT}
    out.update(support_gap_max=0.0, sets=0, outputs_compared=0)
    done = [j for j in jobs if j["ok"]]
    if done:
        out["unstable_bytes"] = compare.unstable_bytes(
            read_files(warm_out), read_files(done[0]["out"]))
    seen: Dict[bytes, Optional[Dict]] = {}
    for job in done:
        blob = read_files(job["out"])
        if blob not in seen:
            try:
                seen[blob] = compare_levels(
                    parse_files(job["out"], sem["max_length"], item_of),
                    inputs.columns, inputs.patterns, sem, seed)
            except (OSError, ValueError, KeyError):
                seen[blob] = None
            out["outputs_compared"] += 1
        one = seen[blob]
        if one is None:
            out["sets_bad"] += 1
            continue
        inputs.seen = one
        out["sets"] = one["sets"]
        for name in ("support_wrong", "sets_surplus", "closure_broken",
                     "sets_missing"):
            out[name] += int(one[name])
        out["support_gap_max"] = max(out["support_gap_max"],
                                     one["support_gap_max"])
    return out


# ----------------------------------------------------------------- control
def to_bfloat16(x: float) -> float:
    """`x` cut to bfloat16's eight bits of mantissa."""
    bits = np.asarray(x, np.float32).view(np.uint32) & np.uint32(0xFFFF0000)
    return float(bits.view(np.float32))


def control_numbers(cell, seed, jobs, dtype="bfloat16") -> Dict:
    """The numbers a run would compare, had the program answered with the
    reference's own sets, every support kept in `dtype` before it is
    printed: one precision below the configuration's float64 quotient of
    integer counts. Draws the cell's baskets at the cell's own size and
    mines them with the reference; runs no job."""
    cfg = cell.config
    sem = ref.job_semantics(cfg["properties"])
    cols, pats = ref.draw_columns(cell.bench_dir, seed,
                                  int(cfg["train_rows"]), cfg["generator"])
    levels: Levels = {}
    for k, kept in enumerate(ref.mine(cols, sem["threshold"],
                                      sem["max_length"]), start=1):
        cut = to_bfloat16 if dtype == "bfloat16" else float
        levels[k] = [(s, f"{cut(c / cols.n):.6f}") for s, c in kept]
    one = compare_levels(levels, cols, pats, sem, seed)
    out = {name: 0 for name in EXACT}
    out.update({name: one[name] for name in (
        "support_wrong", "sets_surplus", "closure_broken", "sets_missing",
        "support_gap_max", "sets")})
    return out
