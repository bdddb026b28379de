"""`correct`, end to end on the CPU at 2,048 train x 256 test rows: the
program's job through `run_from_cli` against the plain reference, through
the comparison a chip run uses; the lower-precision control, which has to
fail it; and the harness driven with the timed path broken underneath,
which has to fail it too. The harness's look for a chip is skipped:
`run_cell` is handed the CPU as its device."""

import os

import numpy as np
import pytest

from bench_fixtures import (CELL_OF, CONFIGS, CPU_DEVICE, compile_cache_off,
                            small_copy)

from chipbench import compare, reference, run
from chipbench.checks import knn_classify


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The small copy, with the persistent compile cache off while these
    tests run (`bench_fixtures.compile_cache_off`)."""
    tmp = tmp_path_factory.mktemp("bench")
    with compile_cache_off():
        yield small_copy(str(tmp)), str(tmp)


def drive(bench, cell_name, seed, entry=run.default_entry, seconds=0.0):
    man, tmp = bench
    return run.run_cell(man.cell(cell_name), man, seed, seconds, False,
                        dict(CPU_DEVICE), entry=entry,
                        work_root=os.path.join(tmp, "work"))


def checked(result):
    return {k: v["value"] for k, v in result["checked"].items() if k != "_seen"}


def test_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as fh:
        text = fh.read()
    assert "avenir_tpu" not in text.replace("`avenir_tpu`", "")


@pytest.mark.parametrize("seed", [2**31 + 5, 6])
@pytest.mark.parametrize("cell", sorted(CELL_OF.values()))
def test_program_agrees_with_the_reference(bench, cell, seed):
    res = drive(bench, cell, seed=seed, seconds=0.2)
    assert res["correct"] is True, res["checked"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    got = checked(res)
    assert got["lines_bad"] == 0 and got["unstable_bytes"] == 0
    assert got["class_flips"] == 0 and got["unresolved"] == 0
    # plain vote: shares are fifths, so agreement is exact; weighted:
    # to the three decimals the line prints
    assert got["share_gap_max"] <= (0.0 if "ccw" not in cell else 0.0011)
    assert set(res["metrics"]) == {"job_s", "setup_s"}
    assert res["metrics"]["job_s"]["value"] > 0
    assert list(res)[-1] == "checked"
    assert res["device"] == dict(CPU_DEVICE, memory_peak_bytes=0)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("config", CONFIGS)
def test_control_in_bfloat16_comes_out_not_correct(bench, config, seed):
    man, _ = bench
    cell = man.cell(CELL_OF[config])
    limits = cell.config["check"]["limits"]
    same = knn_classify.control_numbers(cell, seed, jobs=2, dtype="float32")
    good, _ = compare.verdict(same, limits)
    assert good, same
    low = knn_classify.control_numbers(cell, seed, jobs=2, dtype="bfloat16")
    good, rows = compare.verdict(low, limits)
    assert not good
    # it fails on the number the limit was set for, by a wide margin
    assert low["share_gap_max"] >= 2 * limits["share_gap_max"]


# --------------------------------------------------------------- the faults
def altered_answer(argv):
    """An answer altered where it is produced: the classifier's scores of
    one test row swapped between the classes."""
    from avenir_tpu.models.knn import NearestNeighborClassifier as C

    real = C.predict

    def predict(self, test):
        codes, scores = real(self, test)
        scores = np.array(scores)
        row = int(np.argmax(np.abs(scores[:, 0] - scores[:, 1])))
        scores[row] = scores[row, ::-1]
        codes = np.array(codes)
        codes[row] = 1 - codes[row]
        return codes, scores

    C.predict = predict
    try:
        run.default_entry(argv)
    finally:
        C.predict = real


def half_the_corpus(argv):
    """Half of the batch left out: the index is built over the first half
    of the train rows, and the vote taken over what is left."""
    train = argv[3]
    with open(train) as fh:
        lines = fh.readlines()
    half = train + ".half"
    with open(half, "w") as fh:
        fh.writelines(lines[:len(lines) // 2])
    run.default_entry(argv[:3] + [half] + argv[4:])


def half_the_answers(argv):
    """Half of the test rows never answered."""
    run.default_entry(argv)
    with open(argv[-1]) as fh:
        lines = fh.readlines()
    with open(argv[-1], "w") as fh:
        fh.writelines(lines[:len(lines) // 2])


def one_line_twice(argv):
    """A row answered with its neighbour's line."""
    run.default_entry(argv)
    with open(argv[-1]) as fh:
        lines = fh.readlines()
    lines[3] = lines[2]
    with open(argv[-1], "w") as fh:
        fh.writelines(lines)


def crashes(argv):
    raise RuntimeError("the job fell over")


FAULTS = {"altered_answer": (altered_answer, "share_gap_max"),
          "half_the_corpus": (half_the_corpus, "share_gap_max"),
          "half_the_answers": (half_the_answers, "lines_bad"),
          "one_line_twice": (one_line_twice, "lines_bad")}


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_comes_out_not_correct(bench, config, fault):
    entry, number = FAULTS[fault]
    man, _ = bench
    res = drive(bench, CELL_OF[config], seed=77, entry=entry)
    assert res["correct"] is False
    limit = man.cell(CELL_OF[config]).config["check"]["limits"][number]
    assert checked(res)[number] > limit, res["checked"]


def test_a_job_that_fails_is_counted_and_not_correct(bench):
    calls = []

    def entry(argv):
        calls.append(argv)
        if len(calls) > 1:              # the warm-up goes through
            crashes(argv)
        run.default_entry(argv)

    res = drive(bench, "knn-elearn.bulk", seed=78, entry=entry)
    assert res["correct"] is False
    assert res["attempted"] == 1 and res["failed"] == 1


def test_unstable_output_is_seen(bench):
    state = {"n": 0}

    def entry(argv):
        run.default_entry(argv)
        state["n"] += 1
        if state["n"] == 2:             # the window's first job
            with open(argv[-1], "a") as fh:
                fh.write("\n")

    res = drive(bench, "knn-elearn.bulk", seed=79, entry=entry)
    assert res["correct"] is False and checked(res)["unstable_bytes"] > 0


# ------------------------------------------------------ the comparison alone
def test_ties_at_the_kth_distance_may_go_either_way():
    ref = {"k": 2, "kernel": "none", "kernel_param": 1.0,
           "class_cond_weighted": False}
    dist = np.array([[0.1, 0.2, 0.2, 0.5]], np.float32)
    labels = np.array([[0, 0, 1, 1]])
    post = np.ones((1, 4))
    sets, open_end = reference.neighbour_sets(dist[0], 2, 1e-6)
    assert sets == [(0, 1), (0, 2)] and not open_end
    for shares, code in ([1.0, 0.0], 0), ([0.5, 0.5], 0), ([0.5, 0.5], 1):
        got = knn_classify.compare_sample(np.array([code]), np.array([shares]),
                                     dist, labels, post, ref, 2, 1e-6, 0.05)
        assert got["share_gap_max"] == 0 and got["class_flips"] == 0
        assert got["ties"] == 1 and got["unresolved"] == 0
    wrong = knn_classify.compare_sample(np.array([1]), np.array([[0.0, 1.0]]),
                                   dist, labels, post, ref, 2, 1e-6, 0.05)
    assert wrong["share_gap_max"] == 0.5      # no choice gives all to pass
    clear = np.array([[0.1, 0.2, 0.3, 0.5]], np.float32)
    wrong = knn_classify.compare_sample(np.array([1]), np.array([[0.0, 1.0]]),
                                   clear, labels, post, ref, 2, 1e-6, 0.05)
    assert wrong == {"share_gap_max": 1.0, "class_flips": 1,
                     "unresolved": 0, "ties": 0}
    # a tie that runs past the candidates kept cannot be settled
    _, open_end = reference.neighbour_sets(
        np.array([0.1, 0.2, 0.2, 0.2], np.float32), 2, 1e-6)
    assert open_end


def test_output_lines_are_held_to_their_format():
    ids, classes = ["S1", "S2", "S3"], ["fail", "pass"]
    good = "S1,fail,fail:0.600,pass:0.400\nS2,pass,fail:0.000,pass:1.000\n" \
           "S3,fail,fail:1.000,pass:0.000\n"
    bad, codes, shares = knn_classify.parse_output(good, ids, classes)
    assert bad == 0 and list(codes) == [0, 1, 0] and shares[0, 0] == 0.6
    for broken in (good.replace("S2", "S9"),              # another row's id
                   good.replace("pass:1.000", "pass:0.900"),   # not one
                   good.replace("S1,fail", "S1,pass"),    # not its share's
                   good.replace("fail:1.000", "fail:x"),
                   good + "S4,fail,fail:1.000,pass:0.000\n",
                   good[:good.index("S3")]):
        assert knn_classify.parse_output(broken, ids, classes)[0] == 1
    assert reference.format_line("S1", np.array([3.0, 2.0]), classes) == \
        "S1,fail,fail:0.600,pass:0.400"


def test_verdict_fails_a_number_that_was_not_produced():
    good, rows = compare.verdict({"a": 0}, {"a": 0, "b": 0.1})
    assert not good and [r["ok"] for r in rows] == [True, False]
    assert compare.verdict({"a": float("nan")}, {"a": 1})[0] is False
    assert compare.verdict({"a": 0.05, "b": 0}, {"a": 0.05, "b": 0})[0] is True


@pytest.mark.parametrize("config", CONFIGS)
def test_the_reference_reads_the_jobs_own_properties(bench, config):
    """What the job has to compute is stated once, in `properties`."""
    man, _ = bench
    cfg = man.cell(CELL_OF[config]).config
    assert cfg["reference"] == {"kind": "knn_classify"}
    ref = knn_classify.reference_of(cfg["properties"])
    assert ref["k"] == 5 and ref["metric"] == "manhattan"
    weighted = "ccw" in config
    assert ref["class_cond_weighted"] is weighted
    assert ref["kernel"] == ("gaussian" if weighted else "none")
    assert ref["kernel_param"] == (30.0 if weighted else 1.0)
    assert knn_classify.reference_of({}) == {
        "k": 5, "metric": "manhattan", "kernel": "none", "kernel_param": 1.0,
        "class_cond_weighted": False}


def test_shares_may_name_the_classes_in_any_order():
    ids, classes = ["S1"], ["fail", "pass"]
    bad, codes, shares = knn_classify.parse_output(
        "S1,pass,pass:0.600,fail:0.400\n", ids, classes)
    assert bad == 0 and codes[0] == 1 and list(shares[0]) == [0.4, 0.6]
    assert knn_classify.parse_output(
        "S1,pass,pass:0.600,pass:0.400\n", ids, classes)[0] == 1
