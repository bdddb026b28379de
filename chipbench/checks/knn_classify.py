"""The check of a kNN classification job (`nearestNeighbor`): the output
files the timed jobs wrote against the plain reference (`reference.py`),
number by number. Found by `reference.kind` in the configuration's file.

  lines_bad       output lines that are missing, surplus, out of the test
                  file's order, carry another row's id, or do not parse as
                  `id,class,c0:share,c1:share` with shares that add to one
                  and a class that is the larger share's      (all lines)
  unstable_bytes  bytes that differ between two jobs' outputs on the same
                  inputs (the warm-up job and the window's first)
  share_gap_max   over the sampled rows, the largest distance from the
                  line's class shares to the nearest right answer's
  class_flips     sampled rows whose class no right answer has, where the
                  reference's shares are further apart than the gap limit
  unresolved      sampled rows whose tie at the k-th distance ran past the
                  candidates the reference kept (nothing to compare with)

A right answer is the reference's line for any choice among train rows
level with the k-th nearest (`reference.neighbour_sets`): with whole
numbers in every field, rows at equal distance are common at 1e7 train
rows, and which of them a top-k keeps is not part of the job's contract.

What the job has to compute is read from its `properties`, the same the
program reads, so the two cannot drift apart (`reference_of`).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from chipbench import compare, generate
from chipbench import reference as ref_impl

_TRUE = ("true", "yes", "1")
#: the numbers of which the lower-precision control has to fail one
CONTROL_FAILS = ("share_gap_max", "class_flips")


def reference_of(properties: Dict[str, str]) -> Dict:
    """The job's semantics from its `nen.*` properties, with the job's own
    defaults. The distance is manhattan: the job has no other."""
    def get(key, default):
        return properties.get("nen." + key, default)
    return {"k": int(get("top.match.count", "5")),
            "metric": "manhattan",
            "kernel": get("kernel.function", "none"),
            "kernel_param": float(get("kernel.param", "1.0")),
            "class_cond_weighted":
                get("class.condtion.weighted", "false").lower() in _TRUE
                or get("class.condition.weighted", "false").lower() in _TRUE}


def sizes(cell, inputs) -> Dict:
    """The semantic sizes of the traced job (the window's first): valid
    queries, valid train rows, attributes, neighbours, kernel calls."""
    return {"nq": len(inputs.tests[0]), "n": len(inputs.train),
            "d": inputs.train.q.shape[1],
            "k": reference_of(cell.config["properties"])["k"],
            "kernel_calls": 1}


def parse_output(text: str, ids: Sequence[str], classes: Sequence[str]
                 ) -> Tuple[int, np.ndarray, np.ndarray]:
    """(lines_bad, class code [n], shares [n, K]) of one output file
    against the ids of its test file, in order. The shares may name the
    classes in any order (the program lists them as it met them in the
    data). A bad line's row reads class -1 and shares NaN."""
    lines = text.split("\n")
    if lines and lines[-1] == "":
        lines.pop()
    n, k = len(ids), len(classes)
    bad = abs(len(lines) - n)
    codes = np.full((n,), -1, np.int64)
    shares = np.full((n, k), np.nan)
    for i in range(min(n, len(lines))):
        f = lines[i].split(",")
        ok = len(f) == 2 + k and f[0] == ids[i] and f[1] in classes
        row = [None] * k
        if ok:
            for item in f[2:]:
                name, _, val = item.partition(":")
                try:
                    row[classes.index(name)] = float(val)
                except ValueError:
                    ok = False
            ok = ok and None not in row
        if ok:
            code = classes.index(f[1])
            # three decimals each: the sum is one to a rounding each, and
            # the class named holds a largest share
            ok = abs(sum(row) - 1.0) <= 0.0005 * k + 1e-9 \
                and row[code] >= max(row) - 0.001 - 1e-9
        if not ok:
            bad += 1
            continue
        codes[i] = code
        shares[i] = row
    return bad, codes, shares


def compare_sample(codes: np.ndarray, shares: np.ndarray,
                   dist: np.ndarray, labels: np.ndarray, post: np.ndarray,
                   ref: Dict, n_classes: int, tie_tol: float,
                   gap_limit: float) -> Dict[str, float]:
    """The sampled rows' numbers. `codes` [S] and `shares` [S, K] are the
    program's lines; `dist`, `labels`, `post` [S, keep] the reference's
    nearest candidates by ascending distance."""
    k = int(ref["k"])
    gap_max, flips, unresolved, ties = 0.0, 0, 0, 0
    # the common case in one sweep: no row level with the k-th
    plain = ref_impl.class_scores(dist[:, :k], labels[:, :k], post[:, :k],
                                  ref, n_classes)
    dk = dist[:, k - 1]
    tied = (np.abs(dist - dk[:, None]) <= tie_tol).sum(axis=1) > 1 \
        if dist.shape[1] > k else np.zeros(len(dk), bool)
    for i in range(len(codes)):
        if codes[i] < 0:
            continue                       # counted under lines_bad
        if tied[i]:
            ties += 1
            sets, open_end = ref_impl.neighbour_sets(dist[i], k, tie_tol)
            if open_end:
                unresolved += 1
            answers = [ref_impl.class_scores(
                dist[i, list(s)], labels[i, list(s)], post[i, list(s)],
                ref, n_classes) for s in sets]
        else:
            answers = [plain[i]]
        best_gap, class_ok = np.inf, False
        for sc in answers:
            sh = ref_impl.shares_of(sc)
            best_gap = min(best_gap, float(np.max(np.abs(sh - shares[i]))))
            top = np.sort(sh)[::-1]
            close = len(top) > 1 and top[0] - top[1] <= 2 * gap_limit
            class_ok = class_ok or close or int(np.argmax(sc)) == codes[i]
        gap_max = max(gap_max, best_gap)
        flips += 0 if class_ok else 1
    return {"share_gap_max": gap_max, "class_flips": flips,
            "unresolved": unresolved, "ties": ties}


def reference_candidates(cfg: Dict, train_values: np.ndarray,
                         train_y: np.ndarray, queries: np.ndarray,
                         dtype: str = "float32"
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(distance, class, posterior) [S, keep] of each query's nearest
    train rows by the plain reference, distances in `dtype`."""
    ref = reference_of(cfg["properties"])
    keep = max(int(cfg["check"]["keep"]), ref["k"] + 3)
    feats = generate.feature_fields(cfg["schema"])
    ranges = np.array([f["max"] - f["min"] for f in feats], np.float32)
    n_classes = len(cfg["generator"]["classes"])
    y = train_y.astype(np.int64)
    dist, idx = ref_impl.topk_manhattan(queries, train_values, ranges, keep,
                                        dtype=dtype)
    if ref["class_cond_weighted"]:
        mean, std = ref_impl.nb_fit(train_values, y, n_classes)
        flat = idx.ravel()
        post = ref_impl.feature_prob(train_values[flat], y[flat], mean,
                                     std).reshape(idx.shape)
    else:
        post = np.ones(idx.shape)
    return dist, y[idx], post


def answers_of(dist: np.ndarray, labels: np.ndarray, post: np.ndarray,
               ref: Dict, ids: Sequence[str], classes: Sequence[str]) -> str:
    """The output file a job would write whose nearest candidates are
    these: the first k of each row, no tie considered. The control's
    answers come from here."""
    k = int(ref["k"])
    scores = ref_impl.class_scores(dist[:, :k], labels[:, :k], post[:, :k],
                                   ref, len(classes))
    return "".join(ref_impl.format_line(rid, sc, classes) + "\n"
                   for rid, sc in zip(ids, scores))


def numbers(cell, inputs, seed: int, jobs: List[Dict], warm_out: str
            ) -> Dict[str, float]:
    """The numbers `compare.verdict` holds against the configuration's
    limits, from the output files of the window's jobs."""
    cfg = cell.config
    chk, ref = cfg["check"], reference_of(cfg["properties"])
    classes = inputs.classes
    out: Dict[str, float] = {"lines_bad": 0, "unstable_bytes": 0}
    done = [j for j in jobs if j["ok"]]
    parsed = []
    for j in done:
        with open(j["out"]) as fh:
            text = fh.read()
        bad, codes, shares = parse_output(
            text, inputs.tests[j["file"]].ids(inputs.prefix), classes)
        out["lines_bad"] += bad
        parsed.append((codes, shares))
    if done and done[0]["file"] == 0:
        with open(warm_out, "rb") as a, open(done[0]["out"], "rb") as b:
            out["unstable_bytes"] = compare.unstable_bytes(a.read(), b.read())
    if not done:
        return out

    picks = compare.sample_picks(
        seed, [len(inputs.tests[j["file"]]) for j in done],
        int(chk["sample_rows"]))
    queries = np.concatenate([inputs.tests[j["file"]].values(p)
                              for j, p in zip(done, picks)])
    codes = np.concatenate([c[p] for (c, _), p in zip(parsed, picks)])
    shares = np.concatenate([s[p] for (_, s), p in zip(parsed, picks)])

    dist, labels, post = reference_candidates(
        cfg, inputs.train.values(), inputs.train.y, queries)
    out.update(compare_sample(
        codes, shares, dist, labels, post, ref, len(classes),
        float(chk["tie_tol"]), float(chk["limits"]["share_gap_max"])))
    out["sampled"] = len(codes)
    return out


def control_numbers(cell, seed: int, jobs: int, dtype: str = "bfloat16"
                    ) -> Dict[str, float]:
    """The numbers a run of `jobs` jobs would compare, had the program
    answered with the reference computed in `dtype`: the control, one
    precision below the configuration's. Runs no job of the program."""
    cfg, mix = cell.config, cell.traffic
    gen, chk = cfg["generator"], cfg["check"]
    ref = reference_of(cfg["properties"])
    fields = generate.feature_fields(cfg["schema"])
    classes = list(gen["classes"])
    train = generate.make_csv(None, seed, 0, int(cfg["train_rows"]), gen,
                              fields, 0, cell.bench_dir)
    values = train.values()
    lengths = generate.file_rows(mix)
    picks = compare.sample_picks(
        seed, [lengths[j % len(lengths)] for j in range(jobs)],
        int(chk["sample_rows"]))
    queries, ids = [], []
    for j, pick in enumerate(picks):
        file_no = j % len(lengths)
        rows = generate.make_csv(
            None, seed, 1 + file_no, lengths[file_no], gen, fields,
            int(gen["test_id_start"]) + file_no * int(gen["test_id_stride"]),
            cell.bench_dir)
        queries.append(rows.values(pick))
        all_ids = rows.ids(gen["id_prefix"])
        ids += [all_ids[i] for i in pick]
    queries = np.concatenate(queries)
    truth = reference_candidates(cfg, values, train.y, queries)
    lowered = reference_candidates(cfg, values, train.y, queries, dtype)
    text = answers_of(*lowered, ref, ids, classes)
    bad, codes, shares = parse_output(text, ids, classes)
    out = {"lines_bad": bad, "unstable_bytes": 0}
    out.update(compare_sample(
        codes, shares, *truth, ref, len(classes), float(chk["tie_tol"]),
        float(chk["limits"]["share_gap_max"])))
    out["sampled"] = len(codes)
    return out
