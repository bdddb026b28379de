"""Decision tree / random forest: split enumeration, learning, model format."""

import hashlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from avenir_tpu import obs
from avenir_tpu.core.dataset import Dataset
from avenir_tpu.core.schema import FeatureSchema
from avenir_tpu.data import generate_churn, churn_schema
from avenir_tpu.models import tree as tree_mod
from avenir_tpu.native import ingest
from avenir_tpu.runner import run_job
from avenir_tpu.utils.devices import device_report
from chipbench import forest_reference as ref
from chipbench import generate
from avenir_tpu.models.tree import (
    DecisionPathList,
    DecisionTreeBuilder,
    RandomForestBuilder,
    enumerate_splits,
    _set_partitions,
)

HANGUP_SCHEMA = FeatureSchema.from_json({
    "fields": [
        {"name": "id", "ordinal": 0, "id": True, "dataType": "string"},
        {"name": "custType", "ordinal": 1, "dataType": "categorical",
         "feature": True, "maxSplit": 2,
         "cardinality": ["business", "residence"]},
        {"name": "holdTime", "ordinal": 2, "dataType": "int", "feature": True,
         "min": 0, "max": 600, "bucketWidth": 60, "maxSplit": 2,
         "splitScanInterval": 200},
        {"name": "hungup", "ordinal": 3, "dataType": "categorical",
         "cardinality": ["no", "yes"]},
    ]
})


def hangup_data(n, seed=0):
    """hold time > 300 and residence -> mostly hangs up."""
    rng = np.random.default_rng(seed)
    ct = rng.integers(0, 2, n)
    ht = rng.integers(0, 600, n)
    p = 0.08 + 0.75 * ((ht > 300) & (ct == 1)) + 0.1 * (ht > 300)
    y = (rng.random(n) < p).astype(int)
    rows = [
        [f"c{i}", ["business", "residence"][ct[i]], str(ht[i]),
         ["no", "yes"][y[i]]]
        for i in range(n)
    ]
    return Dataset.from_rows(rows, HANGUP_SCHEMA)


class TestSplitEnumeration:
    def test_set_partitions_binary(self):
        parts = _set_partitions(["a", "b", "c"], 2)
        # 3 ways to 2-partition a 3-set
        assert len(parts) == 3
        for groups in parts:
            assert sorted(sum(groups, [])) == ["a", "b", "c"]
            assert len(groups) == 2

    def test_numeric_split_predicates(self):
        splits = enumerate_splits(HANGUP_SCHEMA)
        num = [s for s in splits if s.attribute == 2]
        # scan interval 200 over (0,600) -> points {200,400}, maxSplit 2 ->
        # two 2-segment splits
        assert len(num) == 2
        s0 = num[0]
        assert s0.predicates[0].to_string() == "2 lt 200"
        assert s0.predicates[1].to_string() == "2 ge 200"
        col = np.array([0, 199, 200, 599], dtype=np.float32)
        np.testing.assert_array_equal(s0.segment_of(col), [0, 0, 1, 1])

    def test_categorical_split_predicates(self):
        splits = enumerate_splits(HANGUP_SCHEMA)
        cat = [s for s in splits if s.attribute == 1]
        assert len(cat) == 1
        assert cat[0].predicates[0].operator == "in"
        col = np.array([0, 1, 0])
        segs = cat[0].segment_of(col)
        assert segs[0] != segs[1] and segs[0] == segs[2]


class TestTreeLearning:
    def test_learns_planted_rule(self):
        ds = hangup_data(4000, seed=1)
        tree = DecisionTreeBuilder(
            HANGUP_SCHEMA, split_algorithm="giniIndex", max_depth=2,
            attr_selection_strategy="notUsedYet",
        ).fit(ds)
        test = hangup_data(1000, seed=2)
        pred = tree.predict(test, ["no", "yes"])
        acc = (pred == test.labels()).mean()
        assert acc > 0.75

    def test_depth_one_picks_oracle_best_split(self):
        ds = hangup_data(3000, seed=3)
        tree = DecisionTreeBuilder(
            HANGUP_SCHEMA, split_algorithm="giniIndex", max_depth=1
        ).fit(ds)
        # numpy oracle: weighted gini of every candidate split
        y = ds.labels()
        splits = enumerate_splits(HANGUP_SCHEMA)
        best, best_score = None, np.inf
        for si, sp in enumerate(splits):
            seg = sp.segment_of(np.asarray(ds.column(sp.attribute)))
            score = 0.0
            for s in range(sp.n_segments):
                m = seg == s
                if m.sum() == 0:
                    continue
                p = np.bincount(y[m], minlength=2) / m.sum()
                score += m.sum() / len(y) * (1 - (p ** 2).sum())
            if score < best_score:
                best, best_score = sp, score
        attrs = {p.predicates[0].attribute for p in tree.paths if p.predicates}
        assert attrs == {best.attribute}
        # the chosen segment predicates match the oracle split's
        got = sorted(p.predicates[0].to_string() for p in tree.paths)
        want = sorted(pr.to_string() for pr in best.predicates)
        assert got == want

    def test_entropy_vs_gini_both_work(self):
        ds = hangup_data(2000, seed=4)
        for algo in ("entropy", "giniIndex"):
            tree = DecisionTreeBuilder(
                HANGUP_SCHEMA, split_algorithm=algo, max_depth=2
            ).fit(ds)
            assert len(tree.paths) >= 2

    def test_populations_sum_to_n(self):
        ds = hangup_data(1500, seed=5)
        tree = DecisionTreeBuilder(HANGUP_SCHEMA, max_depth=2).fit(ds)
        assert sum(p.population for p in tree.paths) == 1500

    def test_min_population_stops(self):
        ds = hangup_data(500, seed=6)
        tree = DecisionTreeBuilder(
            HANGUP_SCHEMA, max_depth=4, stopping_strategy="minPopulation",
            min_population=10_000,
        ).fit(ds)
        # root can never split
        assert len(tree.paths) == 1 and tree.paths[0].predicates == []


class TestModelFormat:
    def test_json_roundtrip(self, tmp_path):
        ds = hangup_data(2000, seed=7)
        tree = DecisionTreeBuilder(HANGUP_SCHEMA, max_depth=2).fit(ds)
        p = tmp_path / "decPathOut.txt"
        tree.save(str(p))
        obj = json.load(open(p))
        assert "decisionPaths" in obj
        path0 = obj["decisionPaths"][0]
        assert {"population", "infoContent", "stopped", "classValPr"} <= set(path0)
        again = DecisionPathList.load(str(p))
        test = hangup_data(300, seed=8)
        np.testing.assert_array_equal(
            tree.predict(test, ["no", "yes"]),
            again.predict(test, ["no", "yes"]),
        )

    def test_predicate_strings_reference_format(self):
        ds = hangup_data(1000, seed=9)
        tree = DecisionTreeBuilder(HANGUP_SCHEMA, max_depth=1).fit(ds)
        for path in tree.paths:
            for pr in path.predicates:
                s = pr.to_string()
                parts = s.split(" ")
                assert parts[1] in ("ge", "lt", "gt", "le", "in")


class TestRandomForest:
    def test_forest_beats_chance(self):
        ds = hangup_data(3000, seed=10)
        rf = RandomForestBuilder(
            HANGUP_SCHEMA, num_trees=5, max_depth=2, seed=3
        ).fit(ds)
        test = hangup_data(800, seed=11)
        cm = rf.validate(test, pos_class=1)
        assert cm.accuracy() > 0.72

    def test_sampling_strategies(self):
        ds = hangup_data(800, seed=12)
        for sampling in ("withReplace", "withoutReplace", "none"):
            rf = RandomForestBuilder(
                HANGUP_SCHEMA, num_trees=2, sampling=sampling, max_depth=1
            ).fit(ds)
            assert len(rf.trees) == 2

    def test_churn_end_to_end(self):
        ds = generate_churn(2500, seed=13)
        rf = RandomForestBuilder(
            churn_schema(), num_trees=5, max_depth=3, seed=1,
            cat_partition_cap=32,
        ).fit(ds)
        test = generate_churn(600, seed=14)
        cm = rf.validate(test, pos_class=1)
        assert cm.accuracy() > 0.75


class TestPaddedChildRegression:
    # mixed segment counts: one attr maxSplit=3, another maxSplit=2, so the
    # tensorized level pass pads children of the 2-segment split; padded
    # slots must never surface as predicate-less catch-all paths
    MIXED_SCHEMA = FeatureSchema.from_json({
        "fields": [
            {"name": "id", "ordinal": 0, "id": True, "dataType": "string"},
            {"name": "a", "ordinal": 1, "dataType": "int", "feature": True,
             "min": 0, "max": 600, "bucketWidth": 60, "maxSplit": 3,
             "splitScanInterval": 200},
            {"name": "b", "ordinal": 2, "dataType": "int", "feature": True,
             "min": 0, "max": 400, "bucketWidth": 40, "maxSplit": 2,
             "splitScanInterval": 200},
            {"name": "cls", "ordinal": 3, "dataType": "categorical",
             "cardinality": ["no", "yes"]},
        ]
    })

    def _data(self, n=400, seed=3):
        rng = np.random.default_rng(seed)
        a = rng.integers(0, 600, n)
        b = rng.integers(0, 400, n)
        y = ((a > 300) & (b > 200)).astype(int)
        rows = [[f"r{i}", str(a[i]), str(b[i]), ["no", "yes"][y[i]]]
                for i in range(n)]
        return Dataset.from_rows(rows, self.MIXED_SCHEMA), y

    def test_no_empty_predicate_paths(self):
        ds, y = self._data()
        model = DecisionTreeBuilder(self.MIXED_SCHEMA, max_depth=3).fit(ds)
        assert all(p.predicates for p in model.paths)
        assert all(p.population > 0 for p in model.paths)

    def test_predict_not_clobbered(self):
        ds, y = self._data()
        model = DecisionTreeBuilder(self.MIXED_SCHEMA, max_depth=3).fit(ds)
        pred = model.predict(ds, ["no", "yes"])
        acc = (np.asarray(pred) == y).mean()
        assert acc > 0.85, f"accuracy collapsed: {acc}"

    def test_no_duplicate_predicates_not_used_yet(self):
        ds, _ = self._data()
        model = DecisionTreeBuilder(
            self.MIXED_SCHEMA, max_depth=4,
            attr_selection_strategy="notUsedYet").fit(ds)
        for p in model.paths:
            reprs = [str(pr) for pr in p.predicates]
            assert len(reprs) == len(set(reprs)), f"dup predicates: {reprs}"


class TestDevicePathEvaluator:
    """Tensorized predict must equal the host per-path loop exactly
    (VERDICT r3 item 6: route all rows through all paths' predicates as
    one batched comparison, vmap'd over RF trees)."""

    def test_single_tree_matches_host_predict(self):
        from avenir_tpu.models.tree import DevicePathEvaluator

        ds = hangup_data(3000, seed=7)
        tree = DecisionTreeBuilder(HANGUP_SCHEMA, max_depth=3).fit(ds)
        test = hangup_data(800, seed=8)
        host = tree.predict(test, ["no", "yes"])
        dev = DevicePathEvaluator([tree], HANGUP_SCHEMA,
                                  ["no", "yes"]).predict(test)
        np.testing.assert_array_equal(host, dev)

    def test_forest_matches_host_predict(self):
        from avenir_tpu.models.tree import DevicePathEvaluator

        ds = hangup_data(2000, seed=9)
        rf = RandomForestBuilder(HANGUP_SCHEMA, num_trees=4, max_depth=3,
                                 seed=2).fit(ds)
        test = hangup_data(500, seed=10)
        host = rf.predict(test)
        dev = DevicePathEvaluator(rf.trees, HANGUP_SCHEMA,
                                  ["no", "yes"]).predict(test)
        np.testing.assert_array_equal(host, dev)

    def test_rf_predict_device_flag(self):
        ds = hangup_data(1500, seed=11)
        rf = RandomForestBuilder(HANGUP_SCHEMA, num_trees=3, max_depth=2,
                                 seed=3).fit(ds)
        test = hangup_data(400, seed=12)
        np.testing.assert_array_equal(rf.predict(test),
                                      rf.predict(test, device=True))

    def test_loaded_json_tree_on_device(self, tmp_path):
        from avenir_tpu.models.tree import DevicePathEvaluator

        ds = hangup_data(2000, seed=13)
        tree = DecisionTreeBuilder(HANGUP_SCHEMA, max_depth=2).fit(ds)
        p = tmp_path / "tree.json"
        tree.save(str(p))
        again = DecisionPathList.load(str(p))
        test = hangup_data(300, seed=14)
        np.testing.assert_array_equal(
            again.predict(test, ["no", "yes"]),
            DevicePathEvaluator([again], HANGUP_SCHEMA,
                                ["no", "yes"]).predict(test))


# ---------------------------------------------------------------------------
# the level pass: exact counts, in row blocks, one form for every caller;
# the forest against the plain reference (chipbench/forest_reference.py)
# ---------------------------------------------------------------------------
BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "chipbench")
with open(os.path.join(BENCH, "configs", "rf-hangup.json")) as _fh:
    RF_HANGUP = json.load(_fh)


def call_hangup_rows(n, seed, tmp_path):
    """(train file, schema file, codes [n, d], y [n]) of n rows of the
    benchmark's call-hangup deployment, written by its own input module."""
    one_csv = generate.load_module(BENCH, "inputs", "one_csv_bulk")
    module = generate.load_module(BENCH, "generators", "call_hangup")
    gen, schema = RF_HANGUP["generator"], RF_HANGUP["schema"]
    fields = one_csv.feature_fields(schema)
    codes, y = module.draw(generate.seed_for(seed, 0), n, gen, fields)
    ids, unread = module.draw_unread(generate.seed_for(seed, 1), n, gen)
    train = tmp_path / "train.csv"
    train.write_bytes(one_csv.format_rows(ids, unread, codes, y, gen, fields, 6))
    schema_path = tmp_path / "schema.json"
    schema_path.write_text(json.dumps(schema))
    return str(train), str(schema_path), codes, y


def forest_properties(schema_path, **more):
    props = {k: v.format(schema=schema_path)
             for k, v in RF_HANGUP["properties"].items()}
    props.update(more)
    return props


def paths_of(model):
    """{predicates: (population, {class: share})} of a DecisionPathList."""
    return {tuple(ref.predicate_key(p.to_json()) for p in path.predicates):
            (path.population, path.class_val_pr) for path in model.paths}


@pytest.mark.parametrize("rows, trees, depth", [
    (16_384, 4, 2), (16_384, 10, 3), (65_536, 4, 3), (65_536, 10, 2)])
def test_forest_agrees_with_the_plain_reference(tmp_path, rows, trees, depth):
    """Tree by tree and path by path: the same predicates, populations
    equal as integers, shares within 1e-6. Where two candidate splits tie
    in gini within 1e-12 (the issue partitions that differ only in `cable`
    tie exactly among business callers, who never have it) either is
    right: the reference then follows the program's."""
    train, schema_path, codes, y = call_hangup_rows(rows, rows + trees, tmp_path)
    props = forest_properties(schema_path, **{
        "dtb.num.trees": str(trees), "dtb.max.depth.limit": str(depth)})
    forest = run_job("randomForest", props, [train], str(tmp_path / "out")).payload
    got = [paths_of(tr) for tr in forest.trees]
    sem = ref.job_semantics(props)
    assert (sem["trees"], sem["max_depth"]) == (trees, depth)
    weights = ref.bootstrap_weights(0, rows, trees, sem["sampling"])

    def choose(t):
        def pick(preds, allowed, scores):
            for i, s in enumerate(allowed):
                taken = all(preds + (p,) in
                            {k[:len(preds) + 1] for k in got[t]}
                            for p in s["predicates"][:1])
                if taken and scores[i] <= min(scores) + 1e-12:
                    return i
            return int(np.argmin(scores))
        return pick

    want = ref.grow_forest(codes.astype(np.int64), y.astype(np.int64), weights,
                           RF_HANGUP["schema"], 2, sem, 0, choose)
    classes = forest.class_values
    assert len(got) == trees
    for t, paths in enumerate(want):
        assert set(got[t]) == {p["predicates"] for p in paths}, t
        for p in paths:
            population, shares = got[t][p["predicates"]]
            assert population == int(p["counts"].sum())
            for c, name in enumerate(RF_HANGUP["generator"]["classes"]):
                assert abs(shares[name] - p["counts"][c] / p["counts"].sum()) < 1e-6
        assert sorted(classes) == ["F", "T"]


def lines(rng, shape, high, dtype):
    return tree_mod.to_lines(rng.integers(0, high, shape).astype(dtype))


def host_counts(leaf, seg, labels, w, n_leaves, smax, k):
    """[L, NS, S, K] by np.bincount in int64, one split at a time (float64
    weights add whole numbers exactly below 2^53)."""
    base = np.asarray(leaf, np.int64) * smax
    weights = np.asarray(w, np.float64)
    flat = np.stack([
        np.bincount((base + col) * k + labels, weights=weights,
                    minlength=n_leaves * smax * k) for col in seg])
    return np.asarray(np.rint(flat), np.int64).reshape(
        len(seg), n_leaves, smax, k).transpose(1, 0, 2, 3)


def test_counts_are_exact_past_two_to_the_24th():
    """Whole-number weights whose root cells sum above 2^25 and are odd:
    the level pass equals np.bincount in int64 exactly; a float32
    accumulator, which the pass had, does not."""
    rng = np.random.default_rng(5)
    n, ns = 20_000, 3
    leaf = np.zeros(n, np.int32)
    seg = rng.integers(0, 2, (ns, n)).astype(np.int8)
    labels = rng.integers(0, 2, n).astype(np.int32)
    w = (2 * rng.integers(3_000, 5_000, n) + 1).astype(np.int32)
    want = host_counts(leaf, seg, labels, w, 1, 2, 2)
    assert want.min() > 2 ** 25 and (want % 2 == 1).any()
    got = np.asarray(tree_mod._level_histogram(
        *(jnp.asarray(tree_mod.to_lines(a)) for a in (leaf, seg, labels, w)),
        1, 2, 2, digits=tree_mod._weight_digits(w.max())))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    cell = (seg[0] == 0) & (labels == 0)
    in_float32 = np.cumsum(w[cell].astype(np.float32), dtype=np.float32)[-1]
    assert int(in_float32) != want[0, 0, 0, 0]


def test_fit_counts_whole_weights_exactly_and_refuses_fractions():
    ds = hangup_data(4_000, seed=3)
    rng = np.random.default_rng(4)
    w = 2 * rng.integers(4_000, 9_000, len(ds)) + 1
    model = DecisionTreeBuilder(HANGUP_SCHEMA, "giniIndex", max_depth=1
                                ).fit(ds, row_weights=w)
    assert sum(p.population for p in model.paths) == int(w.sum()) > 2 ** 25
    for p in model.paths:
        pred = p.predicates[0]
        col = np.asarray(ds.column(pred.attribute))
        keep = (col < pred.value if pred.operator == "lt" else
                col >= pred.value) if pred.operator != "in" else np.isin(
                    col, [HANGUP_SCHEMA.field_by_ordinal(pred.attribute)
                          .cardinality_index()[v] for v in pred.cat_values])
        assert p.population == int(w[keep].sum())
    for bad in (np.full(len(ds), 0.5), np.full(len(ds), -1.0),
                np.full(len(ds), 2.0 ** 31), np.ones(3)):
        with pytest.raises(ValueError, match="row_weights"):
            DecisionTreeBuilder(HANGUP_SCHEMA).fit(ds, row_weights=bad)


@pytest.mark.parametrize("n, block_lines", [
    (128 * 37 + 5, 8),       # four whole blocks, five lines and a part-line
    (128 * 37 + 5, 64),      # one block only, the lines fewer than a block
    (128 * 64, 64),          # one block exactly
    (100, 8)])               # less than a line
def test_blocked_equals_unblocked(n, block_lines):
    rng = np.random.default_rng(n)
    t, ns, n_leaves, smax, k = 3, 5, 4, 3, 2
    leaf = rng.integers(0, n_leaves, (t, n)).astype(np.int32)
    seg = rng.integers(0, smax, (ns, n)).astype(np.int8)
    labels = rng.integers(0, k, n).astype(np.int32)
    w = rng.integers(0, 300, (t, n)).astype(np.int32)
    args = [jnp.asarray(tree_mod.to_lines(a)) for a in (leaf, seg, labels, w)]
    digits = tree_mod._weight_digits(int(w.max()))
    assert digits == 2
    got = np.asarray(tree_mod._level_histogram_forest(
        *args, n_leaves=n_leaves, smax=smax, k=k, digits=digits,
        block_lines=block_lines))
    for i in range(t):
        np.testing.assert_array_equal(
            got[i], host_counts(leaf[i], seg, labels, w[i], n_leaves, smax, k))
    # the advance, in the same blocks, against the plain gather
    best = rng.integers(-1, ns, (t, n_leaves)).astype(np.int32)
    off = rng.integers(4, 9, (t, n_leaves)).astype(np.int32)
    moved = np.asarray(tree_mod._advance_leaves_forest(
        args[0], args[1], jnp.asarray(best), jnp.asarray(off),
        block_lines=block_lines)).reshape(t, -1)[:, :n]
    split = np.take_along_axis(best, leaf, axis=1)
    under = np.take_along_axis(seg, np.maximum(split, 0), axis=0)
    want = np.where(split >= 0, np.take_along_axis(off, leaf, axis=1) + under,
                    leaf)
    np.testing.assert_array_equal(moved, want)


def test_a_second_forest_job_compiles_nothing_and_repeats_its_bytes(tmp_path):
    train, schema_path, _codes, _y = call_hangup_rows(16_384, 9, tmp_path)
    props = forest_properties(schema_path)
    shas = []
    for out in ("out_a", "out_b"):
        before = device_report()
        res = run_job("randomForest", props, [train], str(tmp_path / out))
        after = device_report()
        shas.append([hashlib.sha256(open(p, "rb").read()).hexdigest()
                     for p in res.outputs])
    assert after["xla_compiles"] == before["xla_compiles"]
    assert shas[0] == shas[1] and len(shas[0]) == 10


@pytest.mark.parametrize("more, holds", [
    ({"dtb.split.attribute.selection.strategy": "notUsedYet",
      "dtb.sub.sampling.strategy": "none"}, "every tree is decTree's"),
    ({"dtb.path.stopping.strategy": "minPopulation",
      "dtb.min.population.limit": "1000000"}, "roots only"),
    ({"dtb.path.stopping.strategy": "minInfoGain",
      "dtb.min.info.gain.limit": "0.4"}, "roots only")])
def test_random_forest_reads_the_keys_dec_tree_reads(tmp_path, more, holds):
    """`dtb.split.attribute.selection.strategy`, `dtb.min.info.gain.limit`
    and `dtb.min.population.limit`, which the forest job used to ignore."""
    train, schema_path, _codes, _y = call_hangup_rows(16_384, 11, tmp_path)
    props = forest_properties(schema_path, **more)
    forest = run_job("randomForest", props, [train], str(tmp_path / "rf")).payload
    if holds == "roots only":
        assert all(len(tr.paths) == 1 and not tr.paths[0].predicates
                   for tr in forest.trees)
        plain = run_job("randomForest", forest_properties(schema_path), [train],
                        str(tmp_path / "rf0")).payload
        assert all(len(tr.paths) > 1 for tr in plain.trees)
    else:
        single = run_job("decTree", props, [train], str(tmp_path / "dt")).payload
        assert len(single.paths) > 1
        for tr in forest.trees:
            assert tr.to_json() == single.to_json()


# ---------------------------------------------------------------------------
# the segment matrix, made on the device from the parser's columns, against
# `CandidateSplit.segment_of` stacked on the host: byte for byte
# ---------------------------------------------------------------------------
def host_segment_matrix(splits, ds):
    """What the host made until PR 32: `segment_of` of every split over
    its whole column, the pad rows 0, in lines."""
    n = len(ds)
    seg = np.zeros((len(splits), -(-n // tree_mod.LANES) * tree_mod.LANES),
                   np.int8)
    for i, sp in enumerate(splits):
        seg[i, :n] = sp.segment_of(np.asarray(ds.column(sp.attribute)))
    return tree_mod.to_lines(seg)


def one_feature(field, column):
    """A dataset of one feature column as the parser leaves it (float32
    numeric, int32 codes) and a binary class."""
    schema = FeatureSchema.from_json({"fields": [
        dict(field, name="x", ordinal=0, feature=True),
        {"name": "y", "ordinal": 1, "dataType": "categorical",
         "cardinality": ["no", "yes"]}]})
    n = len(column)
    return Dataset(schema, {0: column, 1: np.zeros(n, np.int32)}, n)


TENTHS = {"dataType": "double", "min": 0.0, "max": 1.0, "maxSplit": 3,
          "splitScanInterval": 0.1}


def around_the_bounds(field):
    """Each bound's float32 neighbours (the nearest float32 on either
    side of a bound that is none itself), and the values one and two ulps
    under and over each of them."""
    bounds = np.unique(np.concatenate([
        s._bounds for s in enumerate_splits(one_feature(
            field, np.zeros(1, np.float32)).schema)]))
    near = bounds.astype(np.float32)
    vals = [near]
    for toward in (-np.inf, np.inf):
        step = near
        for _ in range(3):
            step = np.nextafter(step, np.float32(toward))
            vals.append(step)
    return bounds, np.concatenate(vals).astype(np.float32)


def segment_case(name, tmp_path):
    rng = np.random.default_rng(len(name))
    if name == "call_hangup_18":
        train, schema_path, _codes, _y = call_hangup_rows(16_384, 3, tmp_path)
        full = json.loads(json.dumps(RF_HANGUP["schema"]))
        full["fields"][-1]["cardinality"] = ["F", "T"]
        return Dataset.from_csv(train, FeatureSchema.from_json(full)), {}
    if name == "tenths_on_and_an_ulp_off":
        bounds, vals = around_the_bounds(TENTHS)
        # 0.1 is no float32: its neighbours lie on both sides of it
        assert (vals.astype(np.float64)[:, None] == bounds[None]).sum() < len(bounds)
        return one_feature(TENTHS, vals), {}
    if name == "missing_value":
        col = rng.random(300).astype(np.float32)
        col[::7] = np.nan
        return one_feature(TENTHS, col), {}
    if name == "rows_not_whole_lines":
        col = rng.random(128 * 5 + 77).astype(np.float32)
        return one_feature(dict(TENTHS, min=-1.0), col - 0.5), {}
    cardinality = [f"v{i}" for i in range(
        5 if name == "three_groups" else tree_mod._SELECT_CODES + 8)]
    field = {"dataType": "categorical", "cardinality": cardinality,
             "maxSplit": 3}
    col = rng.integers(0, len(cardinality), 1_000).astype(np.int32)
    return one_feature(field, col), {"cat_partition_cap": 40}


@pytest.mark.parametrize("name", [
    "call_hangup_18", "tenths_on_and_an_ulp_off", "missing_value",
    "rows_not_whole_lines", "three_groups", "codes_past_the_select_chain"])
def test_device_segment_matrix_is_segment_of_stacked(name, tmp_path):
    ds, more = segment_case(name, tmp_path)
    splits = enumerate_splits(ds.schema, **more)
    want = host_segment_matrix(splits, ds)
    got = np.asarray(tree_mod.segment_matrix(splits, ds))
    assert got.dtype == np.int8 and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    smax = max(s.n_segments for s in splits)
    if name == "call_hangup_18":
        assert len(splits) == 18 and got.shape[0] == 18
    elif name in ("three_groups", "codes_past_the_select_chain"):
        assert smax == 3 and want.max() == 2
        past = len(splits[0]._group_of) > tree_mod._SELECT_CODES
        assert past == (name == "codes_past_the_select_chain")
    elif name == "missing_value":
        assert (want.reshape(len(splits), -1)[:, :len(ds):7] == 0).all()
    elif name == "rows_not_whole_lines":
        assert len(ds) % tree_mod.LANES
        assert (got.reshape(len(splits), -1)[:, len(ds):] == 0).all()
        assert want.max() == 2


def test_up32_is_the_least_float32_not_below_the_bound():
    bounds = np.array([0.1, 1 / 3, 60.0, 16_777_217.0, -0.1, 1e-50, 1e39,
                       -1e39, 0.0])
    up = tree_mod.up32(bounds)
    assert up.dtype == np.float32
    assert (up.astype(np.float64) >= bounds).all()
    with np.errstate(over="ignore"):
        under = np.nextafter(up, np.float32(-np.inf)).astype(np.float64)
    assert (under < bounds).all()
    assert up[2] == 60.0 and up[3] == 16_777_218.0 and np.isinf(up[6])


def test_the_forest_job_writes_the_bytes_of_a_forest_from_the_host_matrix(
        tmp_path, monkeypatch):
    """The size of tests/chipbench/test_forest.py: ten trees over 16,384
    call-hangup rows, once from the device program and once with the
    host's stacked `segment_of` handed to the same level passes."""
    train, schema_path, _codes, _y = call_hangup_rows(16_384, 17, tmp_path)
    props = forest_properties(schema_path)

    def model_bytes(out):
        res = run_job("randomForest", props, [train], str(tmp_path / out))
        assert len(res.outputs) == 10
        return [open(p, "rb").read() for p in res.outputs]

    device = model_bytes("device")
    monkeypatch.setattr(
        tree_mod, "segment_matrix",
        lambda splits, ds, put=jnp.asarray: put(host_segment_matrix(splits, ds)))
    assert model_bytes("host") == device


def test_the_segment_program_has_a_name_the_level_metric_does_not_read(
        tmp_path):
    """The forest job compiles `_segment_lines` as a program of its own
    name; `forest_level_ms_per_job` counts the level programs by theirs
    and must not count this one (its roofline reckons the level passes'
    work only)."""
    import re

    def module_name(jitted, *args, **static):
        return re.search(r"module @(\S+)",
                         jitted.lower(*args, **static).as_text()).group(1)

    train, schema_path, _codes, _y = call_hangup_rows(2_048, 19, tmp_path)
    tree_mod._segment_lines.clear_cache()
    run_job("randomForest", forest_properties(schema_path), [train],
            str(tmp_path / "out"))
    assert tree_mod._segment_lines._cache_size() == 1
    col = jnp.zeros((1, tree_mod.LANES), jnp.float32)
    mine = module_name(
        tree_mod._segment_lines, (col,), jnp.zeros((1, 1), jnp.float32),
        jnp.zeros((1, 1), jnp.int8), np.int32(1), plan=((0, True, 1),))
    ids = jnp.zeros((1, 1, tree_mod.LANES), jnp.int32)
    seg = jnp.zeros((1, 1, tree_mod.LANES), jnp.int8)
    level = [
        module_name(tree_mod._level_histogram_forest, ids, seg, ids[0], ids,
                    n_leaves=1, smax=2, k=2),
        module_name(tree_mod._advance_leaves_forest, ids, seg,
                    jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.int32))]
    assert mine == "jit__segment_lines"
    with open(os.path.join(BENCH, "metrics", "forest_level_ms_per_job.json")) as fh:
        patterns = json.load(fh)["params"]["patterns"]
    assert not any(re.search(p, mine) for p in patterns)
    assert all(any(re.search(p, name) for p in patterns) for name in level)
    assert not any(re.search("_segment_lines", name) for name in level)


def test_under_a_mesh_the_segment_program_runs_on_each_shard(mesh8):
    """The column lines shard over the mesh and the program is
    elementwise: the matrix comes out sharded by lines, equal to the
    host's (lines of padding read 0), and compiles to no collective."""
    from functools import partial

    from avenir_tpu.parallel.mesh import shard_rows

    ds = hangup_data(128 * 13 + 5, seed=1)
    splits = enumerate_splits(HANGUP_SCHEMA)
    put = partial(shard_rows, mesh8)
    seg = tree_mod.segment_matrix(splits, ds, put)
    assert tuple(seg.sharding.spec) == (None, "data")
    want = host_segment_matrix(splits, ds)
    got = np.asarray(seg)
    assert got.shape[1] % 8 == 0 and got.shape[1] >= want.shape[1]
    np.testing.assert_array_equal(got[:, :want.shape[1]], want)
    assert not got[:, want.shape[1]:].any()
    columns, plan, bounds, groups = tree_mod._segment_tables(splits)
    cols = tuple(put(tree_mod.to_lines(np.asarray(ds.column(a), dtype)))
                 for a, dtype in columns)
    hlo = tree_mod._segment_lines.lower(
        cols, jnp.asarray(bounds), jnp.asarray(groups), np.int32(len(ds)),
        plan=plan).compile().as_text()
    assert not any(op in hlo for op in (
        "all-reduce", "all-gather", "all-to-all", "collective-permute"))


# ---------------------------------------------------------------------------
# the bootstrap draws: the native walk of numpy's stream against numpy
# ---------------------------------------------------------------------------
needs_native = pytest.mark.skipif(not ingest.native_available(),
                                  reason="native library unavailable")
#: 2^32 mod n is 296, 1,022,831 and 735,396 for the last three: of the
#: 6.2M and 21.4M values five trees take, hundreds are thrown away there
SAMPLE_ROWS = (1, 127, 129, 1_000, 1_245_185, 4_285_715)
SAMPLE_TREES = 5


def numpy_rule(seed, n, trees):
    """[trees, n]: the sampling rule as `RandomForestBuilder` writes it."""
    rng = np.random.default_rng(seed)
    return np.stack([np.bincount(rng.integers(0, n, n), minlength=n)
                     for _ in range(trees)])


def sample_of(seed, n, trees, sampling="withReplace"):
    """(`_sample`'s weights as [trees, n], its largest, the span's note)."""
    forest = RandomForestBuilder(HANGUP_SCHEMA, num_trees=trees, seed=seed,
                                 sampling=sampling)
    ws, heaviest, how = forest._sample(n)
    assert ws.shape == (trees, -(-n // tree_mod.LANES), tree_mod.LANES)
    flat = ws.reshape(trees, -1)
    assert not flat[:, n:].any()
    return flat[:, :n], heaviest, how


def raw_walk(seed, n, draws):
    """(the first `draws` draws, the 32-bit values thrown away before the
    last of them, the values kept among the first stretch the library
    takes): numpy's raw 64-bit outputs, the low half and then the high
    half of each, every one mapped on its own by Lemire's rule."""
    threshold = (2**32 - n) % n
    first_stretch = draws + draws * threshold // (2**32 - threshold)
    raw = np.random.default_rng(seed).bit_generator.random_raw(
        first_stretch // 2 + 4_096)
    m = np.stack([raw & 0xFFFFFFFF, raw >> 32], axis=1).ravel() * np.uint64(n)
    kept = (m & np.uint64(0xFFFFFFFF)) >= threshold
    last = np.flatnonzero(kept)[draws - 1]
    return ((m[kept][:draws] >> 32).astype(np.int64), int(last) + 1 - draws,
            int(kept[:(first_stretch + 1) // 2 * 2].sum()))


@needs_native
@pytest.mark.parametrize("seed", [0, 5])
@pytest.mark.parametrize("n", SAMPLE_ROWS)
def test_the_native_draws_are_numpys_element_for_element(n, seed):
    """One to five trees, the library's own thread count (`_sample`'s),
    one thread, two, and four a core (rows shared by stripes that run at
    once are added atomically): every count equals `np.bincount(rng.integers(0, n,
    n))` in tree order from one `default_rng(seed)`. Five trees' rule
    holds the rule of fewer as its first rows, the stream being one."""
    want = numpy_rule(seed, n, SAMPLE_TREES)
    for trees in range(1, SAMPLE_TREES + 1):
        got, heaviest, how = sample_of(seed, n, trees)
        np.testing.assert_array_equal(got, want[:trees])
        assert heaviest == want[:trees].max()
        assert how["native"] is True and how["threads"] >= 1
        for threads in (1, 2, 4 * os.cpu_count()):
            ws = np.zeros((trees, n + 3), np.int32)
            walk = ingest.bootstrap_counts_native(
                np.random.default_rng(seed), n, ws, threads)
            np.testing.assert_array_equal(ws[:, :n], want[:trees])
            assert not ws[:, n:].any()
            assert walk.threads == min(threads, max(1, (trees * n + 1) // 2))
            assert (walk.max_weight, walk.rejected) == (
                heaviest, how["rejected"])


@needs_native
def test_the_walk_throws_away_what_a_plain_walk_of_the_raw_stream_does():
    """`rejected` against numpy's raw outputs mapped one by one, which
    also gives `integers`' own values back; and over the grid the first
    stretch is short for some calls and long enough for others, so both
    ways through the library's loop are run."""
    short = []
    for n in SAMPLE_ROWS[2:]:
        for seed in (0, 5):
            for trees in (1, 3):
                draws, thrown, in_first = raw_walk(seed, n, trees * n)
                np.testing.assert_array_equal(
                    draws, np.random.default_rng(seed).integers(0, n, trees * n))
                assert sample_of(seed, n, trees)[2]["rejected"] == thrown
                short.append(in_first < trees * n)
    assert any(short) and not all(short)
    assert sample_of(0, SAMPLE_ROWS[-1], 3)[2]["rejected"] > 300


@needs_native
@pytest.mark.parametrize("n", [1_000, 1_245_185])
@pytest.mark.parametrize("why", ["no library", "draws of 64 bits"])
def test_the_numpy_loop_is_taken_where_the_walk_is_not_numpys(
        monkeypatch, why, n):
    """The native library reported absent, and an `n` past the 32-bit
    rule (simulated where the binding decides): `_sample` runs the written
    rule and gives the same weights as the walk does."""
    native, heaviest, how = sample_of(3, n, 3)
    assert how["native"] is True
    if why == "no library":
        monkeypatch.setattr(ingest, "native_available", lambda: False)
    else:
        monkeypatch.setattr(ingest, "UINT32_DRAWS", n - 1)
    got, top, how = sample_of(3, n, 3)
    assert how == {"native": False, "threads": 1}
    np.testing.assert_array_equal(got, native)
    np.testing.assert_array_equal(got, numpy_rule(3, n, 3))
    assert top == heaviest


@needs_native
def test_a_generator_the_walk_cannot_follow_is_left_to_numpy():
    """Another bit generator, and a PCG64 that holds the unread half of
    an output: nothing is written and the caller falls back."""
    ws = np.zeros((2, 128), np.int32)
    philox = np.random.Generator(np.random.Philox(0))
    assert ingest.bootstrap_counts_native(philox, 100, ws) is None
    half = np.random.default_rng(0)
    half.integers(0, 100, 1)
    assert half.bit_generator.state["has_uint32"] == 1
    assert ingest.bootstrap_counts_native(half, 100, ws) is None
    assert ingest.bootstrap_counts_native(np.random.default_rng(0), 0, ws) is None
    assert not ws.any()
    rng = np.random.default_rng(0)
    before = rng.bit_generator.state
    assert ingest.bootstrap_counts_native(rng, 100, ws).max_weight == ws.max()
    assert rng.bit_generator.state == before
    with pytest.raises(ValueError, match="int32"):
        ingest.bootstrap_counts_native(rng, 100, ws.astype(np.int64))


@pytest.mark.parametrize("sampling", ["withoutReplace", "none"])
def test_the_other_strategies_draw_as_they_did(sampling):
    got, heaviest, how = sample_of(2, 1_000, 3, sampling)
    rng = np.random.default_rng(2)
    want = np.stack([rng.random(1_000) < 0.7 if sampling == "withoutReplace"
                     else np.ones(1_000, bool) for _ in range(3)])
    np.testing.assert_array_equal(got, want)
    assert (heaviest, how) == (1, {"native": False, "threads": 1})


@needs_native
def test_forest_sample_says_how_the_draws_were_made(tmp_path):
    """The span of a forest job carries `native`, `threads` and
    `rejected` beside `sampling`."""
    rows = 16_384
    train, schema_path, _codes, _y = call_hangup_rows(rows, 23, tmp_path)
    with obs.capture() as ring:
        run_job("randomForest", forest_properties(schema_path), [train],
                str(tmp_path / "out"))
    (span,) = [sp for sp in ring.spans() if sp.name == "forest.sample"]
    assert {k: v for k, v in span.attrs.items()
            if k not in obs.USAGE_ATTRS} == {
        "sampling": "withReplace", "native": True,
        "threads": span.attrs["threads"],
        "rejected": raw_walk(0, rows, 10 * rows)[1]}
    assert 1 <= span.attrs["threads"] <= os.cpu_count()
