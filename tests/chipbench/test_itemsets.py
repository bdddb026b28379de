"""The frequent-itemset deployment `fia-t10i4` and its cell
`fia-t10i4.remine` on the CPU at 12,288 baskets: the program's job through
`run_from_cli` against the plain reference (`fia_reference.py`) through the
check a chip run uses (`checks/itemset_supports.py`); the control, which
has to fail it; the harness driven over a broken timed path, which has to
fail it too; the generator's and the input module's bytes; every pin of
`pins.py`; and the new readers over a recorded mining job
(data/events_fia_job.json)."""

import hashlib
import itertools
import json
import os

import numpy as np
import pytest

import pins
from bench_fixtures import CPU_DEVICE, ROOT, compile_cache_off, small_copy

from chipbench import compare, generate, manifest, run
from chipbench import fia_reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "fia-t10i4.remine"
ROWS = 12_288                        # 3 x 2^12: a support is no bfloat16
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    DOC = json.load(_fh)
FIA_METRICS = [m["name"] for m in DOC["per_layer"]
               if m["name"].startswith("fia_")]
EXACT = ("sets_bad", "unstable_bytes", "support_wrong", "sets_surplus",
         "closure_broken", "sets_missing")


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The small copy at 12,288 baskets, the persistent compile cache off
    while these tests run."""
    tmp = tmp_path_factory.mktemp("itemsets")
    with compile_cache_off():
        yield small_copy(str(tmp), train_rows=ROWS), str(tmp)


def drive(bench, seed, entry=run.default_entry, name="work"):
    man, tmp = bench
    return run.run_cell(man.cell(CELL), man, seed, 0.0, False,
                        dict(CPU_DEVICE), entry=entry,
                        work_root=os.path.join(tmp, name))


def checked(result):
    return {k: v["value"] for k, v in result["checked"].items() if k != "_seen"}


def columns_of(baskets, n_items=8):
    """Reference columns of a few baskets given as item lists."""
    pairs = sorted((t, i) for t, b in enumerate(baskets) for i in set(b))
    cols = ref.Columns(len(baskets), n_items)
    cols.put(0, ref.chunk_bits(
        np.array([p[0] for p in pairs], np.int32),
        np.array([p[1] for p in pairs], np.int16), len(baskets), n_items))
    return cols


# ------------------------------------------------------------ the reference
@pytest.mark.parametrize("name", ["fia_reference.py",
                                  "checks/itemset_supports.py",
                                  "inputs/basket_file.py",
                                  "generators/quest_baskets.py",
                                  "readers/fia_roofline.py"])
def test_reference_and_check_import_nothing_of_the_program(name):
    with open(os.path.join(ROOT, "chipbench", name)) as fh:
        text = fh.read()
    assert "import avenir" not in text and "from avenir" not in text
    assert "import jax" not in text


def test_a_support_is_the_popcount_of_the_anded_columns():
    baskets = [[0, 1, 2], [0, 1], [1, 2, 7], [0, 1, 2, 7], [3], [0, 2],
               [0, 1, 2], [1], [0, 1, 7], [2, 7]] * 3 + [[5, 6]]
    cols = columns_of(baskets)
    sets = [set(b) for b in baskets]
    for k in (1, 2, 3):
        for cand in itertools.combinations(range(8), k):
            want = sum(1 for s in sets if s.issuperset(cand))
            assert cols.count(cand) == want
    assert cols.item_counts().tolist() == [cols.count((i,)) for i in range(8)]
    many = list(itertools.combinations(range(8), 2)) * 5
    assert cols.counts(many) == [cols.count(s) for s in many]
    assert cols.counts([]) == []


def test_join_and_prune_is_aprioris():
    pairs = [(0, 1), (0, 2), (1, 2), (1, 7), (2, 7), (0, 7)]
    assert ref.join_and_prune(pairs, 3) == [(0, 1, 2), (0, 1, 7), (0, 2, 7),
                                            (1, 2, 7)]
    assert ref.join_and_prune(pairs[:4], 3) == [(0, 1, 2)]   # no (2, 7)
    assert ref.join_and_prune([(3,), (1,), (2,)], 2) == [(1, 2), (1, 3), (2, 3)]
    assert ref.join_and_prune([], 2) == []
    # the program's own join over the same sets, in the same order
    from avenir_tpu.models.association import _generate_candidates

    assert _generate_candidates(pairs, 3) == ref.join_and_prune(pairs, 3)


def test_mining_stops_at_the_first_empty_length_and_counts_over():
    baskets = [[0, 1, 2]] * 3 + [[0, 1]] * 2 + [[3]] * 5
    cols = columns_of(baskets)
    levels = ref.mine(cols, 0.3, 3)            # over 3 of 10: 4 and more
    assert [[s for s, _c in lv] for lv in levels] == [
        [(0,), (1,), (3,)], [(0, 1)]]
    assert levels[0] == [((0,), 5), ((1,), 5), ((3,), 5)]
    assert ref.over(4, 0.3, 10) and not ref.over(3, 0.3, 10)
    assert ref.line(["I001", "I042"], 5 / 10) == "I001,I042,0.500000"


# --------------------------------------------------- the cell, end to end
@pytest.mark.parametrize("seed", [2**31 + 33, 7, 19])
def test_program_agrees_with_the_reference(bench, seed):
    res = drive(bench, seed)
    assert res["correct"] is True, res["checked"]
    assert res["attempted"] == 1 and res["failed"] == 0
    got = checked(res)
    assert all(got[k] == 0 for k in EXACT)
    assert got["support_gap_max"] == 0.0
    seen = res["checked"]["_seen"]
    assert seen["outputs_compared"] == 1 and seen["sets"] >= 2500
    assert set(res["metrics"]) == {"job_s", "setup_s"}
    work = os.path.join(bench[1], "work", CELL)
    # (the program keeps its block-size record beside the file: .avenir_tune)
    assert sorted(f for f in os.listdir(work) if not f.startswith(".")) == [
        "baskets.csv", "job.properties", "out_000", "out_warmup"]
    assert sorted(os.listdir(os.path.join(work, "out_000"))) == [
        "itemsets-1.txt", "itemsets-2.txt", "itemsets-3.txt"]
    with open(os.path.join(work, "baskets.csv")) as fh:
        row = fh.readline().rstrip("\n").split(",")
    assert len(row[0]) == 10 and row[0].isdigit() and len(row) >= 2
    assert all(len(t) == 4 and t[0] == "I" and t[1:].isdigit() for t in row[1:])
    assert row[1:] == sorted(set(row[1:]))


def test_sizes_are_semantic_and_count_the_candidates(bench):
    man, _tmp = bench
    cell = man.cell(CELL)
    check = man.module("checks", "itemset_supports")

    class Seen:
        n, columns = ROWS, ref.Columns(8, 1000)
        seen = {"frequent": 870, "candidates": {2: 378015, 3: 1500}}

    assert check.sizes(cell, Seen) == {
        "n": ROWS, "items": 1000, "frequent": 870, "max_length": 3,
        "candidates": {2: 378015, 3: 1500}}
    del Seen.seen                      # no output compared: nothing to roof
    assert check.sizes(cell, Seen)["frequent"] == 0


# ------------------------------------------------------------- the control
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_comes_out_not_correct(bench, seed):
    """The reference's own sets with every support kept in bfloat16: its
    eighth bit stands apart from the float64 quotient in the printed
    digits, which fails `support_gap_max`; with float64 supports the same
    control passes."""
    man, _tmp = bench
    cell = man.cell(CELL)
    check = man.module("checks", "itemset_supports")
    limits = cell.config["check"]["limits"]
    low = check.control_numbers(cell, seed, 1, "bfloat16")
    good, _ = compare.verdict(low, limits)
    assert not good
    assert low["support_gap_max"] > 20 * limits["support_gap_max"]
    assert low["support_wrong"] > 0.5 * low["sets"]
    assert all(low[k] == 0 for k in EXACT if k != "support_wrong")
    assert set(check.CONTROL_FAILS) == {"support_gap_max", "support_wrong"}
    same = check.control_numbers(cell, seed, 1, "float64")
    assert compare.verdict(same, limits)[0] and same["support_gap_max"] == 0.0


def test_bfloat16_keeps_eight_bits():
    from chipbench.checks import itemset_supports

    assert itemset_supports.to_bfloat16(0.5) == 0.5
    assert itemset_supports.to_bfloat16(1 / 3) == 0.33203125
    assert itemset_supports.to_bfloat16(0.004528) == 0.0045166015625


# ------------------------------------------------- a broken timed path
def edit(path, change):
    with open(path) as fh:
        lines = fh.readlines()
    with open(path, "w") as fh:
        fh.writelines(change(lines))


def half_the_baskets(argv):
    with open(argv[3]) as fh:
        lines = fh.readlines()
    with open(argv[3] + ".half", "w") as fh:
        fh.writelines(lines[:len(lines) // 2])
    run.default_entry(argv[:3] + [argv[3] + ".half"] + argv[4:])


def a_support_altered(argv):
    run.default_entry(argv)

    def bump(lines):
        toks = lines[3].rstrip("\n").split(",")
        toks[-1] = f"{float(toks[-1]) + 1e-6:.6f}"
        return lines[:3] + [",".join(toks) + "\n"] + lines[4:]

    edit(os.path.join(argv[-1], "itemsets-2.txt"), bump)


def a_frequent_pair_left_out(argv):
    """The first pair that no reported triple holds: its absence breaks
    no closure, and it is a subset of a pattern or it would hardly be
    frequent."""
    run.default_entry(argv)
    with open(os.path.join(argv[-1], "itemsets-3.txt")) as fh:
        inside = {p for ln in fh for p in itertools.combinations(
            ln.split(",")[:3], 2)}

    def drop(lines):
        at = next(i for i, ln in enumerate(lines)
                  if tuple(ln.split(",")[:2]) not in inside)
        return lines[:at] + lines[at + 1:]

    edit(os.path.join(argv[-1], "itemsets-2.txt"), drop)


def a_set_under_the_threshold(argv):
    run.default_entry(argv)
    edit(os.path.join(argv[-1], "itemsets-2.txt"),
         lambda lines: ["I000,I001,0.000000\n"] + [
             ln for ln in lines if not ln.startswith("I000,I001,")])


def a_triple_without_its_pair(argv):
    run.default_entry(argv)
    with open(os.path.join(argv[-1], "itemsets-3.txt")) as fh:
        first = fh.readline().split(",")[:2]
    edit(os.path.join(argv[-1], "itemsets-2.txt"),
         lambda lines: [ln for ln in lines if ln.split(",")[:2] != first])


def lines_out_of_order(argv):
    run.default_entry(argv)
    edit(os.path.join(argv[-1], "itemsets-1.txt"),
         lambda lines: [lines[1], lines[0]] + lines[2:])


def a_length_missing(argv):
    run.default_entry(argv)
    os.remove(os.path.join(argv[-1], "itemsets-2.txt"))


@pytest.mark.parametrize("fault, fails, holds", [
    (half_the_baskets, ["support_wrong"],
     ["sets_bad", "unstable_bytes", "closure_broken"]),
    (a_support_altered, ["support_wrong", "support_gap_max"],
     ["sets_bad", "unstable_bytes", "sets_surplus", "closure_broken",
      "sets_missing"]),
    (a_frequent_pair_left_out, ["sets_missing"],
     ["sets_bad", "unstable_bytes", "support_wrong", "sets_surplus",
      "closure_broken"]),
    (a_set_under_the_threshold, ["sets_surplus", "support_wrong"],
     ["sets_bad", "unstable_bytes", "closure_broken"]),
    (a_triple_without_its_pair, ["closure_broken", "sets_missing"],
     ["sets_bad", "unstable_bytes", "support_wrong", "sets_surplus"]),
    (lines_out_of_order, ["sets_bad"],
     ["unstable_bytes", "support_wrong", "sets_surplus", "closure_broken",
      "sets_missing"]),
    (a_length_missing, ["sets_bad"],
     ["unstable_bytes", "support_wrong", "sets_surplus", "closure_broken",
      "sets_missing"])],
    ids=lambda v: getattr(v, "__name__", None))
def test_a_broken_timed_path_comes_out_not_correct(bench, fault, fails, holds):
    res = drive(bench, 11, entry=fault, name="work_" + fault.__name__)
    got = checked(res)
    assert res["correct"] is False
    assert all(got[k] > (5e-7 if k == "support_gap_max" else 0)
               for k in fails), got
    assert all(got[k] == 0 for k in holds), got


def test_unstable_output_is_seen(bench):
    calls = []

    def entry(argv):
        run.default_entry(argv)
        calls.append(argv[-1])
        if len(calls) == 2:
            with open(os.path.join(argv[-1], "itemsets-3.txt"), "a") as fh:
                fh.write(" ")

    res = drive(bench, 12, entry=entry, name="work_unstable")
    assert res["correct"] is False and checked(res)["unstable_bytes"] >= 1


# ------------------------------------------------------ bytes, by hash
DRAW_SHA256 = "eacc20cf1baecb0f9a95ebed922e29ffad2a3ec9f0e336443943127f74169b40"
FILES_SHA256 = {
    "baskets.csv": "d2bece0cc72642b7ef46ba18be0da2c2dda77681f642fbda3f73b657411af28a",
    "job.properties": "c131e6f79f4a99049c0a4d6016a03a65be4f9e62e030fbaf13a34d699e07cb58",
}


def test_the_generator_and_the_input_module_write_these_bytes(bench, tmp_path):
    """For a fixed seed at 12,288 baskets: what the generator draws, and
    the file and the properties the input module writes."""
    man, _tmp = bench
    cell = man.cell(CELL)
    gen = cell.config["generator"]
    module = man.module("generators", "quest_baskets")
    pats = ref.draw_patterns(module, gen)
    assert pats["items"].shape[0] == 2000 == len(pats["size"])
    assert 3.8 < pats["size"].mean() < 4.2 and pats["size"].min() >= 1
    for row, size in zip(pats["items"], pats["size"]):
        assert len(set(row[:size].tolist())) == size and (row[size:] == -1).all()
    basket, item = ref.draw_chunk(module, 2**31 + 33, 0, ROWS, gen, pats)
    assert basket.dtype == np.int32 and item.dtype == np.int16
    key = basket.astype(np.int64) * 1024 + item
    assert (np.diff(key) > 0).all()            # sorted, each pair once
    held = np.bincount(basket, minlength=ROWS)
    assert held.min() >= 1 and 9.5 < held.mean() < 10.5
    assert hashlib.sha256(basket.tobytes() + item.tobytes()
                          ).hexdigest() == DRAW_SHA256
    work = str(tmp_path)
    inputs = man.inputs(cell.config).Inputs(cell, 2**31 + 33, work)
    np.testing.assert_array_equal(
        inputs.columns.bits, ref.chunk_bits(basket, item, ROWS, 1000))
    got = {}
    for f in os.listdir(work):
        with open(os.path.join(work, f), "rb") as fh:
            got[f] = hashlib.sha256(fh.read()).hexdigest()
    assert got == FILES_SHA256
    at = lambda name: os.path.join(work, name)  # noqa: E731
    assert inputs.n_files == 1 and inputs.out_suffix == "" and inputs.n == ROWS
    assert inputs.argv(0, "OUT") == inputs.warmup_argv("OUT") == [
        "frequentItemsApriori", "--conf", at("job.properties"),
        at("baskets.csv"), "OUT"]
    assert inputs.tokens[:2] == ["I000", "I001"] and len(inputs.tokens) == 1000


def _toy_patterns(items, corruption):
    """Patterns of equal weight over a few items, for the generator's
    own rules."""
    widest = max(len(p) for p in items)
    rows = np.full((len(items), widest), -1, np.int16)
    for p, row in zip(items, rows):
        row[:len(p)] = p
    return {"items": rows, "size": np.array([len(p) for p in items], np.int64),
            "cum_weight": np.cumsum(np.full(len(items), 1.0 / len(items))),
            "corruption": np.asarray(corruption, float)}


def test_a_pattern_that_does_not_fit_goes_in_whole_or_to_the_next_basket():
    """The paper's rule, against a basket-by-basket loop over the same
    draws: a pattern that does not fit is put in whole half the time and
    is otherwise the first on hand in the next basket, as corrupted."""
    module = manifest.Manifest().module("generators", "quest_baskets")
    gen = manifest.Manifest().cell(CELL).config["generator"]
    pats = ref.draw_patterns(module, gen)
    rng = np.random.default_rng(1)
    n, draws = 20_000, module.MOST_DRAWS
    want = np.maximum(rng.poisson(10, n), 1).astype(np.int32)
    _pattern, kept, whole = module._series(rng, want, pats)
    whole_in = rng.random(n) < 0.5
    handed, own, placed = module._handed_on(want, kept, whole, whole_in)
    on_hand = -1
    for b in range(n):
        assert handed[b] == on_hand
        room, goes_in, out = int(want[b]), False, -1
        if on_hand >= 0:
            width = int(kept.reshape(-1)[on_hand])
            if width <= room:
                room, goes_in = room - width, True
            elif whole_in[b]:
                room, goes_in = 0, True
            else:
                room, out = 0, on_hand
        count = 0
        for j in range(draws if room else 0):
            if kept[b, j] <= room:
                room, count = room - int(kept[b, j]), count + 1
                if not room:
                    break
            else:
                if whole[b, j]:
                    count += 1
                else:
                    out = b * draws + j
                break
        assert (own[b], placed[b]) == (count, goes_in)
        on_hand = out
    assert 0.2 < (handed >= 0).mean() < 0.5 and placed.sum() <= (handed >= 0).sum()


def test_a_corrupted_pattern_keeps_a_uniform_subset_of_its_items():
    """One pattern of four items, always cut to two or fewer (corruption
    such that two drops are common): all six pairs occur, about as often;
    a basket never holds an item twice."""
    module = manifest.Manifest().module("generators", "quest_baskets")
    pats = _toy_patterns([[3, 5, 8, 9]], [0.7])
    gen = {"items": 16, "basket_size": 2}
    basket, item = module.draw(np.random.default_rng(5), 60_000, gen, pats)
    held = np.bincount(basket, minlength=60_000)
    two = np.flatnonzero(held == 2)
    first = np.searchsorted(basket, two)
    pairs = item[first].astype(int) * 16 + item[first + 1]
    names, seen = np.unique(pairs, return_counts=True)
    of_pattern = [a * 16 + b for a, b in itertools.combinations([3, 5, 8, 9], 2)]
    share = np.array([seen[names == p].sum() for p in of_pattern]) / len(two)
    assert (share > 0.1).all() and share.max() / share.min() < 1.3
    key = basket.astype(np.int64) * 1024 + item
    assert (np.diff(key) > 0).all()


def test_chunks_join_and_threads_keep_the_order(bench, tmp_path, monkeypatch):
    """The file is the same whatever the chunks' threads did: baskets in
    chunk order, each chunk a generator of its own, and the columns hold
    what the file holds."""
    man, _tmp = bench
    cell = man.cell(CELL)
    monkeypatch.setattr(ref, "CHUNK_ROWS", 4096)
    inputs = man.inputs(cell.config).Inputs(cell, 5, str(tmp_path))
    with open(inputs.train_path) as fh:
        rows = [ln.rstrip("\n").split(",") for ln in fh]
    assert len(rows) == ROWS
    module = man.module("generators", "quest_baskets")
    gen = cell.config["generator"]
    for c, lo, hi in ref.chunks(ROWS):
        basket, item = ref.draw_chunk(module, 5, c, hi - lo, gen,
                                      inputs.patterns)
        for t in (0, hi - lo - 1):
            assert rows[lo + t][1:] == [inputs.tokens[i]
                                        for i in item[basket == t]]
    for t in (0, 4095, 4096, ROWS - 1):
        held = [i for i in range(1000)
                if inputs.columns.bits[i, t // 8] >> (t % 8) & 1]
        assert rows[t][1:] == [inputs.tokens[i] for i in held]


def test_the_pattern_table_is_the_configurations_not_the_seeds(bench):
    """`generator.pattern_seed` draws the 2,000 patterns, `--seed` the
    baskets: two seeds mine the same table, so the frequent items V' (and
    with them the word count and the candidate counts) differ only by the
    items whose support stands within sampling noise of the threshold:
    here, at 2^20 baskets, a few of some 857; at the cell's size, seven
    times less noise. Under a table drawn from the seed they stood 30 and
    more apart (849 to 881 over PR 33's seeds, 27 or 28 words)."""
    man, _tmp = bench
    gen = dict(man.cell(CELL).config["generator"])
    module = man.module("generators", "quest_baskets")
    pats = ref.draw_patterns(module, gen)
    again = ref.draw_patterns(module, gen)
    assert all((pats[k] == again[k]).all() for k in pats)
    other = ref.draw_patterns(module, {**gen, "pattern_seed": 1})
    assert other["items"].shape != pats["items"].shape or (
        other["items"] != pats["items"]).any()
    n, frequent = 1 << 20, []
    for seed in (3, 2**31 + 5):
        counts = np.zeros(1000, np.int64)
        for c, lo, hi in ref.chunks(n):
            _basket, item = ref.draw_chunk(module, seed, c, hi - lo, gen, pats)
            counts += np.bincount(item, minlength=1000)
        frequent.append(int((counts > 0.0033 * n).sum()))
    assert abs(frequent[0] - frequent[1]) <= 12
    assert all(845 <= f <= 864 for f in frequent)       # 27 words


# --------------------------------------------------------------- the pins
def test_the_deployment_through_every_pin():
    man = manifest.Manifest()
    cfg = next(c for c in DOC["configs"] if c["name"] == "fia-t10i4")
    entry = next(w for w in DOC["workloads"] if w["name"] == CELL)
    pins.hold_configuration(man, cfg)
    pins.hold_environment(man, cfg, ROOT)
    pins.hold_cell(man, entry)
    pins.hold_four_chip_share(DOC)
    for m in DOC["per_layer"]:
        if CELL in m.get("workloads", []):
            pins.hold_per_layer_metric(man, m)
    pins.hold_the_first_sixteen(DOC)
    assert cfg["reduced"] == ["train_rows"] and entry["chips"] == 1
    assert (entry["config"], entry["traffic"]) == ("fia-t10i4", "remine-nightly")
    doc = pins.config_doc(man, cfg)
    assert "environment" not in doc and doc["inputs_kind"] == "basket_file"
    assert doc["job"] == "frequentItemsApriori" and "schema" not in doc
    assert set(doc["assumed"]) >= {"train_rows", "generator", "item_tokens",
                                   "item_set_length", "support", "sidecar",
                                   "pattern_seed"}
    # ISSUE 34 names six; the sixth, fia.stream.sidecar=false, is left out
    # as it says: the whole-file route never consults the sidecar
    assert doc["properties"] == {
        "fia.support.threshold": "0.0033", "fia.item.set.length": "3",
        "fia.emit.trans.id": "false", "fia.tans.id.ord": "0",
        "fia.skip.field.count": "1"}
    assert doc["check"]["limits"] == {
        "sets_bad": 0, "unstable_bytes": 0, "support_wrong": 0,
        "sets_surplus": 0, "closure_broken": 0, "sets_missing": 0,
        "support_gap_max": 5e-07}
    # the paper's T10.I4: N 1000, |L| 2000, correlation and corruption 0.5
    gen = doc["generator"]
    assert (gen["items"], gen["patterns"]) == (1000, 2000)
    assert (gen["basket_size"], gen["pattern_size"]) == (10, 4)
    assert (gen["correlation"], gen["corruption_mean"],
            gen["corruption_variance"]) == (0.5, 0.5, 0.1)
    assert gen["pattern_seed"] == 19940912
    assert doc["properties"]["fia.support.threshold"] in (
        "0.02", "0.015", "0.01", "0.0075", "0.005", "0.0033", "0.0025")
    assert doc["train_rows"] % 2**20 == 0
    assert 40 * 2**20 <= doc["train_rows"] <= 72 * 2**20
    mix = man.cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["files_per_seed"]) == ("closed", 1, 1)
    assert mix["rows_per_file"] == doc["train_rows"]
    # a basket on the device: 26 words and more of 32 frequent items, 4 B
    # each: 30% of 16 GiB or more
    assert doc["train_rows"] * 26 * 4 >= 0.30 * 2**34
    assert len(doc["source"]) <= 200 and len(entry["why"]) <= 200


def test_the_cells_per_layer_metrics_are_these_thirteen():
    """The four it shares, then the nine, in their order among whatever a
    later PR adds; the nine with their layers, in `per_layer` in that
    order too, and on no other cell (`pins.hold_fia_names`). Their
    places are not held: new entries go at the end of the list."""
    pins.hold_fia_names(manifest.Manifest())
    assert FIA_METRICS[:9] == pins.FIA_NAMES


def test_the_cell_joins_no_list_whose_span_its_jobs_thread_does_not_own():
    """`parse_ms_per_job` sums `stream.parse`, which the streamed route
    records a block (PR 33's cell read 54 s of it in a 24 s job, summed
    over worker threads); the resident route records none, and the cell
    is not on that list nor on any other kNN or forest metric's."""
    joined = {m["name"] for m in DOC["per_layer"]
              if CELL in m["workloads"] and not m["name"].startswith("fia_")}
    assert joined >= set(pins.SHARED_FOUR)
    assert "parse_ms_per_job" not in joined
    assert not any(n.startswith(("knn_", "nb_", "forest_", "mesh_"))
                   for n in joined)


# ------------------------------------------- the readers, a recorded job
@pytest.fixture()
def ctx():
    with open(os.path.join(HERE, "data", "events_fia_job.json")) as fh:
        recorded = json.load(fh)
    ann = recorded["annotations"][0]
    return {"spans": recorded["spans"], "devices": recorded["devices"],
            "window_ns": (ann[1], ann[1] + ann[2]), "jobs": 1, "notes": {},
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "sizes": {"n": 1_000_000, "items": 1000, "frequent": 800,
                      "max_length": 3, "candidates": {2: 319_600, 3: 2_000}}}


def read(ctx, name):
    man = manifest.Manifest()
    spec = man.metric(name)
    return man.reader(spec["reader"])(ctx, spec["params"])


#: pairs: 2 x 1e6 x 319,600 operations at 197 TFLOP/s over 8 ms; sets:
#: 1e6 x 100 + 4 x 2,000 bytes at 819 GB/s over 2 ms
PAIRS = 100.0 * (2e6 * 319_600 / 197e12) / 8e-3
SETS = 100.0 * ((1e8 + 8_000) / 819e9) / 2e-3
EXPECTED = {
    "fia_read_ms_per_job": 12.0,
    "fia_scan_ms_per_job": 40.0,               # the two passes: 18 + 22
    "fia_put_ms_per_job": 6.0,
    "fia_candidates_ms_per_job": 3.5,
    "fia_support_ms_per_job": 10.0,            # the Gram 8, the triples 2
    "fia_pairs_roofline": PAIRS,
    "fia_sets_roofline": SETS,
    # 0.3 + 0.5 + 0.5 + 0.5 + 1 + 5.5 + 8.8
    "fia_unspanned_ms_per_job": 17.1,
    # the leaves cover 82.5 ms, the device is busy for 12 of them, and
    # idle for 88 of the window's 100
    "fia_idle_named_share": 100.0 * (82.5 - 12.0) / 88.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_fia_metric_reads_the_recorded_job(ctx, name):
    assert sorted(EXPECTED) == sorted(pins.FIA_NAMES)
    assert read(ctx, name) == pytest.approx(EXPECTED[name], rel=1e-6)


def test_the_accepted_encode_metric_reads_zero_in_a_mining_job(ctx):
    """The miner has no `dataset.encode` span: the accepted `span_sum`
    finds spans and none of that name."""
    assert read(ctx, "train_encode_ms_per_job") == 0.0


def test_the_rooflines_know_only_semantic_sizes_and_say_their_bound(ctx):
    roofline = manifest.Manifest().module("readers", "fia_roofline")
    assert roofline.pairs_work(1_000_000, 800) == (
        2e6 * 319_600, 1e6 * 100 + 4 * 319_600)
    assert roofline.sets_work(1_000_000, 800, {2: 319_600, 3: 2_000}) == (
        2e6 * 2_000 * 3, 1e8 + 8_000)
    assert roofline.sets_work(10, 8, {"3": 5, "4": 2}) == (
        2 * 10 * (5 * 3 + 2 * 4), 2 * 10.0 + 4 * 7)
    assert 35 < read(ctx, "fia_pairs_roofline") < 45
    assert 5 < read(ctx, "fia_sets_roofline") < 7
    assert ctx["notes"] == {"fia_pairs_roofline_bound": "compute",
                            "fia_sets_roofline_bound": "memory"}
    ctx["sizes"].update(slab_words=32768, dtype="bfloat16", block_words=8192)
    assert read(ctx, "fia_pairs_roofline") == pytest.approx(PAIRS)
    # a full Gram does twice the pairs' count: at the MXU's peak the share
    # reads half
    full_gram_s = 2.0 * 1e6 * 800 * 800 / 197e12
    ops, _bytes = roofline.pairs_work(1_000_000, 800)
    assert ops / 197e12 / full_gram_s < 0.5


@pytest.mark.parametrize("name", ["fia_pairs_roofline", "fia_sets_roofline",
                                  "fia_support_ms_per_job"])
def test_where_no_support_program_ran_nothing_is_returned_never_zero(ctx, name):
    dev = ctx["devices"]["/device:TPU:0"]
    dev["modules"] = [m for m in dev["modules"]
                      if "_pair_gram" not in m[0] and "_set_supports" not in m[0]]
    assert read(ctx, name) is None
    ctx["devices"] = {}
    assert read(ctx, name) is None


def test_a_round_that_did_not_run_has_no_roofline(ctx):
    ctx["sizes"]["candidates"] = {2: 319_600}
    assert read(ctx, "fia_sets_roofline") is None
    ctx["sizes"]["frequent"] = 0               # no output was compared
    assert read(ctx, "fia_pairs_roofline") is None


@pytest.mark.parametrize("name", ["fia_unspanned_ms_per_job",
                                  "fia_idle_named_share"])
def test_a_program_without_the_spans_leaves_the_metric_out(ctx, name):
    """The parent commit has no `fia.*` span; its `job.cli` alone still
    joins the clocks, and a program with no `job.cli` returns nothing."""
    ctx["spans"] = [s for s in ctx["spans"] if not s["name"].startswith("fia.")]
    assert read(ctx, name) is not None
    ctx["spans"] = []
    assert read(ctx, name) is None


def test_both_fia_lists_name_the_same_leaves_and_no_parent():
    man = manifest.Manifest()
    kids = man.metric("fia_unspanned_ms_per_job")["params"]["children"]
    assert kids == man.metric("fia_idle_named_share")["params"]["leaves"]
    assert len(set(kids)) == len(kids) == 7
    for parent in ("job.cli", "job.run", "fia.mine"):
        assert parent not in kids
    assert {"fia.read", "fia.scan", "fia.put", "fia.round.fetch",
            "fia.output.write"} <= set(kids)
