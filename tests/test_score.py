"""avenir-score: the micro-batched online scoring plane (server/score.py).

The contract under test is BIT-IDENTITY: a row scored through the
coalescing plane — whatever window it lands in — must equal the batch
predictor job's output line for that row, for every scoreable family.
Plus the plumbing the plane rides: the warm ModelCache (exclusive
checkout, digest invalidation, format-skew refusal), the reward journal
(atomic append, nonce exactly-once, fold algebra), the HTTP/1.1
keep-alive ``POST /score`` edge, and the metrics merge.
"""

import http.client
import json
import math
import os
import threading

import numpy as np
import pytest

from avenir_tpu.data import churn_schema, generate_churn
from avenir_tpu.models.artifact import (ModelFormatSkew, rm_stamp,
                                        stamp_path, write_stamp)
from avenir_tpu.runner import run_job
from avenir_tpu.server.score import (ModelCache, ScoreError, ScorePlane,
                                     ScoreRequest, _ModelEntry,
                                     append_reward, fold_rewards,
                                     load_reward_journal, model_cache_key,
                                     reward_journal_path, score_once,
                                     score_request_from_json)

MST_CONF = {"mst.model.states": "L,M,H",
            "mst.class.label.field.ord": "1",
            "mst.skip.field.count": "2",
            "mst.class.labels": "T,F"}

MARKOV_SCORE_CONF = {"field.delim": ",", "class.labels": "T,F",
                     "log.odds.threshold": "0", "skip.field.count": "2"}

BANDIT_SCORE_CONF = {"field.delim": ",", "algorithm": "greedyRandomBandit",
                     "batch.size": "2", "round": "50",
                     "random.selection.prob": "0.0"}


# ---------------------------------------------------------------- fixtures
def _seq_csv(tmp_path, rows=240, seed=12, name="seq.csv"):
    rng = np.random.default_rng(seed)
    states = ["L", "M", "H"]
    csv = tmp_path / name
    with open(csv, "w") as fh:
        for i in range(rows):
            up = i % 2 == 0
            s, toks = 1, []
            for _ in range(6):
                p = [0.1, 0.3, 0.6] if up else [0.6, 0.3, 0.1]
                s = int(np.clip(s + rng.choice([-1, 0, 1], p=p), 0, 2))
                toks.append(states[s])
            fh.write(f"c{i},{'T' if up else 'F'}," + ",".join(toks) + "\n")
    return str(csv)


def _markov_model(tmp_path):
    train = _seq_csv(tmp_path, name="train.csv")
    model = str(tmp_path / "mst_model.txt")
    run_job("markovStateTransitionModel", dict(MST_CONF), [train], model)
    return model


def _bandit_stats(tmp_path, name="stats.csv"):
    path = str(tmp_path / name)
    with open(path, "w") as fh:
        for g in ("g1", "g2", "g3"):
            fh.write(f"{g},itemA,10,5.0\n{g},itemB,10,1.0\n"
                     f"{g},itemC,4,3.0\n")
    return path


def _plane_scores(plane, reqs, timeout=60.0):
    """Fire every request concurrently (so windows actually coalesce)
    and return results in request order."""
    out = [None] * len(reqs)
    errs = []

    def worker(i, req):
        try:
            out[i] = plane.score(req, timeout=timeout)
        except BaseException as exc:           # surfaced to the assert
            errs.append(exc)

    threads = [threading.Thread(target=worker, args=(i, r))
               for i, r in enumerate(reqs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    return out


# ------------------------------------------------------- family parity
def test_markov_score_matches_batch_classifier(tmp_path):
    model = _markov_model(tmp_path)
    test = _seq_csv(tmp_path, rows=40, seed=77, name="test.csv")
    out = str(tmp_path / "batch_out.txt")
    run_job("markovModelClassifier",
            {"mmc.mm.model.path": model, "mmc.class.labels": "T,F",
             "mmc.skip.field.count": "2"}, [test], out)
    batch = open(out).read().splitlines()
    rows = open(test).read().splitlines()

    plane = ScorePlane(window_ms=20.0, batch_max=8)
    try:
        reqs = [ScoreRequest("markov", model, r, dict(MARKOV_SCORE_CONF))
                for r in rows]
        got = [res.row for res in _plane_scores(plane, reqs)]
    finally:
        plane.close()
    # coalesced-window output is BIT-identical to the batch job's lines
    assert got == batch
    # ... and to a cold solo score (window of one)
    assert score_once("markov", model, rows[0],
                      dict(MARKOV_SCORE_CONF)) == batch[0]


def test_bayes_score_matches_batch_predictor(tmp_path):
    schema = str(tmp_path / "churn.json")
    churn_schema().save(schema)
    train, test = str(tmp_path / "train.csv"), str(tmp_path / "test.csv")
    with open(train, "w") as fh:
        fh.write(generate_churn(400, seed=3, as_csv=True))
    with open(test, "w") as fh:
        fh.write(generate_churn(40, seed=4, as_csv=True))
    res = run_job("bayesianDistr",
                  {"bad.feature.schema.file.path": schema}, [train],
                  str(tmp_path / "distr") + os.sep)
    model = res.outputs[0]          # fold output: a LEGACY unstamped file
    out = str(tmp_path / "pred.txt")
    run_job("bayesianPredictor",
            {"bap.feature.schema.file.path": schema,
             "bap.bayesian.model.file.path": model}, [test], out)
    batch = open(out).read().splitlines()
    rows = open(test).read().splitlines()

    conf = {"schema.path": schema, "field.delim": ","}
    plane = ScorePlane(window_ms=20.0, batch_max=16)
    try:
        got = [res.row for res in _plane_scores(
            plane, [ScoreRequest("bayes", model, r, dict(conf))
                    for r in rows])]
        assert got == batch         # unstamped artifact loads AND matches
        # a row Dataset.from_csv would silently drop (blank) or split
        # (embedded newline) ERRORS instead of shifting the demux ...
        for bad in ("   ", rows[0] + "\n" + rows[1]):
            with pytest.raises(ScoreError):
                plane.score(ScoreRequest("bayes", model, bad,
                                         dict(conf)), timeout=30.0)
        # ... and the dispatcher survives it: the next score still serves
        again = plane.score(ScoreRequest("bayes", model, rows[0],
                                         dict(conf)), timeout=30.0)
        assert again.row == batch[0]
    finally:
        plane.close()


def test_discriminant_score_matches_batch_predict(tmp_path):
    from avenir_tpu.data import elearn_schema, generate_elearn
    from avenir_tpu.models.discriminant import FisherDiscriminant

    schema = str(tmp_path / "elearn.json")
    elearn_schema().save(schema)
    ds = generate_elearn(200, seed=5)
    lines = []
    for i in range(len(ds)):
        toks = []
        for fld in ds.schema.fields:
            col = ds.column(fld.ordinal)
            if fld.is_categorical:
                toks.append(fld.decode_value(int(col[i])))
            elif fld.is_numeric:
                v = float(col[i])
                toks.append(str(int(v)) if v == int(v) else f"{v:.4f}")
            else:
                toks.append(str(col[i]))
        lines.append(",".join(toks))
    train = str(tmp_path / "train.csv")
    with open(train, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    model = str(tmp_path / "fisher.txt")
    run_job("fisherDiscriminant",
            {"fid.feature.schema.file.path": schema}, [train], model)

    fd = FisherDiscriminant.load(model)
    ordinal = sorted(fd.boundaries)[0]
    rows = open(train).read().splitlines()[:24]
    x = np.asarray([float(r.split(",")[ordinal]) for r in rows],
                   np.float64)
    want = fd.predict_values(ordinal, x)

    conf = {"field.delim": ",", "ordinal": str(ordinal)}
    plane = ScorePlane(window_ms=20.0, batch_max=8)
    try:
        got = [res.row for res in _plane_scores(
            plane, [ScoreRequest("discriminant", model, r, dict(conf))
                    for r in rows])]
    finally:
        plane.close()
    for row, r_in, side in zip(got, rows, want):
        assert row == r_in + "," + str(int(side))


def test_bandit_score_matches_batch_job(tmp_path):
    stats = _bandit_stats(tmp_path)
    out = str(tmp_path / "select.txt")
    run_job("greedyRandomBandit",
            {"grb.global.batch.size": "2", "grb.current.round.num": "50",
             "grb.random.selection.prob": "0.0"}, [stats], out)
    by_group = {}
    for ln in open(out).read().splitlines():
        by_group.setdefault(ln.split(",")[0], []).append(ln)

    plane = ScorePlane(window_ms=20.0, batch_max=8)
    try:
        got = _plane_scores(
            plane, [ScoreRequest("bandit", stats, g,
                                 dict(BANDIT_SCORE_CONF))
                    for g in ("g1", "g2", "g3")])
    finally:
        plane.close()
    for g, res in zip(("g1", "g2", "g3"), got):
        assert res.row == "\n".join(by_group[g])
    with pytest.raises(ScoreError):
        score_once("bandit", stats, "no_such_group",
                   dict(BANDIT_SCORE_CONF))


# -------------------------------------------------------- coalescing
def test_concurrent_scores_coalesce_into_bounded_dispatches(tmp_path):
    model = _markov_model(tmp_path)
    rows = open(_seq_csv(tmp_path, rows=24, seed=9, name="q.csv")
                ).read().splitlines()
    solo = [score_once("markov", model, r, dict(MARKOV_SCORE_CONF))
            for r in rows]

    plane = ScorePlane(window_ms=200.0, batch_max=8)
    try:
        got = [res.row for res in _plane_scores(
            plane, [ScoreRequest("markov", model, r,
                                 dict(MARKOV_SCORE_CONF))
                    for r in rows])]
        calls = plane.predict_calls(model)
        snap = plane.snapshot()
    finally:
        plane.close()
    assert got == solo
    # M concurrent scores for one model coalesce into at most
    # ceil(M / batch_max) vectorized dispatches
    assert calls <= math.ceil(len(rows) / 8)
    assert snap["stats"]["scores"] == len(rows)
    assert snap["stats"]["window_rows"] == len(rows)
    # one load served every window (warm cache, not per-request parse)
    assert snap["stats"]["model_loads"] == 1


def test_short_predict_demuxes_error_and_dispatcher_survives(tmp_path,
                                                             monkeypatch):
    """A predict that returns fewer rows than the window has slots is a
    demuxed per-slot ScoreError — never an escaped IndexError that
    kills the sole dispatcher thread and wedges the plane for good."""
    import avenir_tpu.server.score as score_mod

    model = str(tmp_path / "fake_model.txt")
    open(model, "w").write("anything\n")

    class _FlakyScorer:
        short = True
        nbytes = 64

        def __init__(self, model_path, conf):
            pass

        def predict_rows(self, rows):
            if _FlakyScorer.short:
                return list(rows)[:-1]          # one row vanishes
            return [r + ",ok" for r in rows]

    monkeypatch.setitem(score_mod._SCORERS, "markov", _FlakyScorer)
    plane = ScorePlane(window_ms=0.0)
    try:
        with pytest.raises(ScoreError, match="demux"):
            plane.score(ScoreRequest("markov", model, "a,b", {}),
                        timeout=30.0)
        # the error was counted, the thread lived, the plane still serves
        assert plane.snapshot()["stats"]["errors"] == 1
        _FlakyScorer.short = False
        res = plane.score(ScoreRequest("markov", model, "a,b", {}),
                          timeout=30.0)
        assert res.row == "a,b,ok"
    finally:
        plane.close()                  # a wedged dispatcher would raise


def test_score_request_rejects_blank_and_multiline_rows():
    base = {"kind": "markov", "model": "m.txt"}
    assert score_request_from_json({**base, "row": "a,b"}).row == "a,b"
    for bad in ("", "   \t", "a,b\nc,d", "a,b\rc,d"):
        with pytest.raises(ValueError):
            score_request_from_json({**base, "row": bad})


# ----------------------------------------------------- warm model cache
def test_model_cache_exclusive_checkout_and_eviction():
    cache = ModelCache(budget_bytes=100)
    a = _ModelEntry(("a",), object(), 60)
    b = _ModelEntry(("b",), object(), 60)
    cache.checkin(a)
    # checkout POPS: a second checkout of the same key misses — the
    # budget sweep can never see (so never unload) a checked-out model
    assert cache.checkout(("a",)) is a
    assert cache.checkout(("a",)) is None
    cache.checkin(b)                   # over budget only once a returns
    assert cache.snapshot()["entries"] == 1
    cache.checkin(a)                   # 120 > 100: LRU (b) evicted
    snap = cache.snapshot()
    assert snap["entries"] == 1 and snap["evictions"] == 1
    assert cache.checkout(("b",)) is None
    assert cache.checkout(("a",)) is a


def test_retrain_changes_cache_key_and_forces_reload(tmp_path):
    model = _markov_model(tmp_path)
    k1 = model_cache_key("markov", model, dict(MARKOV_SCORE_CONF))
    row = open(_seq_csv(tmp_path, rows=4, seed=9, name="q.csv")
               ).read().splitlines()[0]
    plane = ScorePlane(window_ms=0.0)
    try:
        plane.score(ScoreRequest("markov", model, row,
                                 dict(MARKOV_SCORE_CONF)))
        # retrain over different data: artifact digest moves -> the
        # warm entry is unreachable (key MISS), never stale
        train2 = _seq_csv(tmp_path, rows=240, seed=99, name="t2.csv")
        run_job("markovStateTransitionModel", dict(MST_CONF), [train2],
                model)
        k2 = model_cache_key("markov", model, dict(MARKOV_SCORE_CONF))
        assert k2 != k1
        got = plane.score(ScoreRequest("markov", model, row,
                                       dict(MARKOV_SCORE_CONF)))
        assert plane.snapshot()["stats"]["model_loads"] == 2
        assert got.row == score_once("markov", model, row,
                                     dict(MARKOV_SCORE_CONF))
    finally:
        plane.close()
    # conf dims are key dims too
    assert model_cache_key(
        "markov", model,
        {**MARKOV_SCORE_CONF, "log.odds.threshold": "5"}) != k2


def test_format_skew_refuses_and_unstamped_loads(tmp_path):
    model = _markov_model(tmp_path)
    row = open(_seq_csv(tmp_path, rows=4, seed=9, name="q.csv")
               ).read().splitlines()[0]
    want = score_once("markov", model, row, dict(MARKOV_SCORE_CONF))
    # a FOREIGN format_version in the stamp refuses the load outright
    stamp = json.load(open(stamp_path(model)))
    stamp["format_version"] = 99
    json.dump(stamp, open(stamp_path(model), "w"))
    with pytest.raises(ModelFormatSkew):
        score_once("markov", model, row, dict(MARKOV_SCORE_CONF))
    # an UNSTAMPED artifact (pre-stamp seed data) still loads
    rm_stamp(model)
    assert score_once("markov", model, row,
                      dict(MARKOV_SCORE_CONF)) == want
    # restamping at this build's version verifies again
    write_stamp(model)
    assert score_once("markov", model, row,
                      dict(MARKOV_SCORE_CONF)) == want
    # a digest mismatch (artifact edited under a valid stamp) refuses
    with open(model, "a") as fh:
        fh.write("\n")
    with pytest.raises(ModelFormatSkew):
        score_once("markov", model, row, dict(MARKOV_SCORE_CONF))


# -------------------------------------------------------- reward journal
def test_reward_journal_append_fold_and_nonce(tmp_path):
    stats = _bandit_stats(tmp_path)
    ack = append_reward(stats, "g1", "itemB", 9.0, count=2, nonce="n1")
    assert ack == {"applied": True, "entries": 1}
    # the SAME nonce dedupes: a retried append is exactly-once
    assert append_reward(stats, "g1", "itemB", 9.0, count=2,
                         nonce="n1") == {"applied": False, "entries": 1}
    append_reward(stats, "g2", "itemA", 2.0)
    assert len(load_reward_journal(stats)) == 2

    from avenir_tpu.models.bandits import GroupBanditData
    rows = [[t.strip() for t in ln.split(",")]
            for ln in open(stats).read().splitlines()]
    data = GroupBanditData.from_rows(rows, count_ord=2, reward_ord=3)
    gi = list(data.group_ids).index("g1")
    ai = list(data.item_ids[gi]).index("itemB")
    before = float(data.rewards[gi, ai])
    fold_rewards(data, load_reward_journal(stats))
    # counts add; avg reward re-weights by the observation count
    assert int(data.counts[gi, ai]) == 12
    assert float(data.rewards[gi, ai]) == pytest.approx(
        (before * 10 + 9.0) / 12, rel=1e-6)
    with pytest.raises(ScoreError):
        fold_rewards(data, [{"group": "gX", "item": "i", "reward": 1.0}])


def test_append_refuses_to_publish_over_corrupt_journal(tmp_path):
    stats = _bandit_stats(tmp_path)
    append_reward(stats, "g1", "itemB", 9.0, nonce="n1")
    with open(reward_journal_path(stats), "w") as fh:
        fh.write("{torn")
    # READERS treat unparseable as absent (racing delete/truncation)...
    assert load_reward_journal(stats) == []
    # ...but the WRITER's read-extend-publish must not overwrite reward
    # history it cannot read with a journal of only the new entry
    with pytest.raises(ModelFormatSkew):
        append_reward(stats, "g2", "itemA", 1.0)
    assert open(reward_journal_path(stats)).read() == "{torn"


def test_reward_append_shifts_next_bandit_pull(tmp_path):
    stats = _bandit_stats(tmp_path)
    conf = dict(BANDIT_SCORE_CONF, **{"batch.size": "1"})
    before = score_once("bandit", stats, "g1", conf)
    k1 = model_cache_key("bandit", stats, conf)
    plane = ScorePlane(window_ms=0.0)
    try:
        assert plane.score(ScoreRequest("bandit", stats, "g1",
                                        conf)).row == before
        # a huge observed reward on the cold arm moves the greedy pick;
        # the journal digest is a KEY dim, so the warm stats go
        # unreachable and the next pull folds the new evidence
        plane.reward(ScoreRequest("bandit", stats, "g1,itemB,500,5",
                                  conf, action="reward", req_id="r1"))
        assert model_cache_key("bandit", stats, conf) != k1
        after = plane.score(ScoreRequest("bandit", stats, "g1",
                                         conf)).row
    finally:
        plane.close()
    assert after != before
    assert after.split(",")[1] == "itemB"


# ------------------------------------------------- HTTP edge + metrics
def test_post_score_keepalive_two_requests_one_socket(tmp_path):
    from avenir_tpu.net.listener import NetListener
    from avenir_tpu.server import JobServer

    model = _markov_model(tmp_path)
    rows = open(_seq_csv(tmp_path, rows=4, seed=9, name="q.csv")
                ).read().splitlines()
    want = [score_once("markov", model, r, dict(MARKOV_SCORE_CONF))
            for r in rows[:2]]
    srv = JobServer(state_root=str(tmp_path / "srv"), workers=1)
    try:
        with NetListener(srv, port=0) as lis:
            conn = http.client.HTTPConnection("127.0.0.1", lis.port,
                                              timeout=60)
            socks = []
            for i, row in enumerate(rows[:2]):
                conn.request(
                    "POST", "/score",
                    json.dumps({"kind": "markov", "model": model,
                                "row": row,
                                "conf": MARKOV_SCORE_CONF}).encode(),
                    {"Content-Type": "application/json"})
                resp = conn.getresponse()
                body = json.loads(resp.read())
                assert resp.status == 200 and body["row"] == want[i]
                socks.append(conn.sock)
            # HTTP/1.1 keep-alive: both requests rode ONE socket
            assert socks[0] is socks[1] and socks[0] is not None
            # unknown field -> strict 400, still on the same socket
            conn.request("POST", "/score",
                         json.dumps({"kind": "markov", "model": model,
                                     "row": rows[0], "oops": 1}).encode(),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 400
            resp.read()
            assert conn.sock is socks[0]
            conn.close()
    finally:
        srv.shutdown()


def test_score_front_mints_reward_nonce_and_closes_all_threads(tmp_path):
    from avenir_tpu.net.fleet import ScoreFront
    from avenir_tpu.net.listener import NetListener
    from avenir_tpu.server import JobServer

    stats = _bandit_stats(tmp_path)
    srv = JobServer(state_root=str(tmp_path / "srv"), workers=1)
    try:
        with NetListener(srv, port=0) as lis:
            front = ScoreFront([f"http://127.0.0.1:{lis.port}"])
            # reward with NO req_id: the front mints a nonce, so its
            # fresh-connection retry can never double-apply the append
            ack = front.score("bandit", stats, "g1,itemB,9.0,2",
                              conf=dict(BANDIT_SCORE_CONF),
                              action="reward")
            assert ack["applied"] is True
            entries = load_reward_journal(stats)
            assert len(entries) == 1 and entries[0]["nonce"]
            # a keep-alive socket opened by ANOTHER thread is closed
            # by close() too, not leaked until process exit
            t = threading.Thread(
                target=front.score,
                args=("bandit", stats, "g1"),
                kwargs={"conf": dict(BANDIT_SCORE_CONF)})
            t.start()
            t.join()
            conns = list(front._all_conns)
            assert len(conns) == 2            # one per (thread, host)
            front.close()
            assert front._all_conns == []
            assert all(c.sock is None for c in conns)
    finally:
        srv.shutdown()


def test_score_front_pins_each_model_and_the_merge_keeps_both(tmp_path):
    """Two hosts behind a ScoreFront, two models queried side by side
    over keep-alive sockets: each model misses once and then returns to
    the host that holds it loaded, every wire answer equals its solo
    twin, and the merged snapshot counts every score and keeps both
    models' end-to-end histograms."""
    from avenir_tpu.net.fleet import ScoreFront
    from avenir_tpu.net.listener import NetListener
    from avenir_tpu.obs.report import merge_snapshots
    from avenir_tpu.server import JobServer

    models = []
    for m, seed in enumerate((12, 13)):
        train = _seq_csv(tmp_path, seed=seed, name=f"train_{m}.csv")
        model = str(tmp_path / f"mst_model_{m}.txt")
        run_job("markovStateTransitionModel", dict(MST_CONF), [train], model)
        models.append(model)
    rows = open(_seq_csv(tmp_path, rows=20, seed=9, name="q.csv")
                ).read().splitlines()
    solo = {m: [score_once("markov", m, r, dict(MARKOV_SCORE_CONF))
                for r in rows] for m in models}
    servers = [JobServer(state_root=str(tmp_path / f"h{i}"),
                         workers=1).start() for i in range(2)]
    listeners = [NetListener(s, port=0).start() for s in servers]
    wire = {m: [] for m in models}
    try:
        front = ScoreFront([f"http://127.0.0.1:{lis.port}"
                            for lis in listeners])

        def client(model):
            for r in rows:
                wire[model].append(front.score(
                    "markov", model, r, conf=dict(MARKOV_SCORE_CONF),
                    timeout=60.0)["row"])

        threads = [threading.Thread(target=client, args=(m,))
                   for m in models]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        routed = dict(front.router.stats)
        front.close()
        snaps = [s.metrics_snapshot() for s in servers]
    finally:
        for lis in listeners:
            lis.stop()
        for srv in servers:
            srv.shutdown()
    assert wire == solo
    total = len(models) * len(rows)
    assert routed["affinity_misses"] == len(models)
    assert routed["affinity_hits"] == total - len(models)
    # one load a model over the whole fleet: no model was scored on two hosts
    assert sum((s.get("score") or {}).get("stats", {}).get("model_loads", 0)
               for s in snaps) == len(models)
    merged = merge_snapshots(snaps)
    assert merged["score"]["stats"]["scores"] == total
    for m in models:
        name = os.path.splitext(os.path.basename(m))[0]
        assert merged["hists"][f"score_{name}_total_ms"]["count"] \
            == len(rows)
        assert merged["score"]["per_model_predicts"][name] >= 1


def test_metrics_snapshot_and_fleet_merge_carry_score(tmp_path):
    from avenir_tpu.obs.report import merge_snapshots
    from avenir_tpu.server import JobServer

    model = _markov_model(tmp_path)
    row = open(_seq_csv(tmp_path, rows=4, seed=9, name="q.csv")
               ).read().splitlines()[0]
    srv = JobServer(state_root=str(tmp_path / "srv"), workers=1)
    try:
        plane = srv.score_plane(window_ms=0.0)
        plane.score(ScoreRequest("markov", model, row,
                                 dict(MARKOV_SCORE_CONF)))
        snap = srv.metrics_snapshot()
    finally:
        srv.shutdown()
    assert snap["score"]["stats"]["scores"] == 1
    name = os.path.splitext(os.path.basename(model))[0]
    assert f"score_{name}_total_ms" in snap["hists"]
    assert snap["score"]["per_model_predicts"][name] == 1
    # fleet merge: score counters sum, per-model hists fold exactly
    merged = merge_snapshots([snap, snap])
    assert merged["score"]["stats"]["scores"] == 2
    assert merged["score"]["per_model_predicts"][name] == 2
    assert merged["hists"][f"score_{name}_total_ms"]["count"] == 2
