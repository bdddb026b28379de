"""avenir-net: listener backpressure, affinity routing, fleet, roll-up.

The PR's contracts:
1. Listener — the HTTP edge round-trips the spool request/result JSON
   byte-identically to the solo runner; /metrics serves the live
   snapshot, /healthz the drain state.
2. Edge load-shed — a flood priced over budget is answered 429 with
   Retry-After (or held, per policy) at the EDGE; the server's priced
   peak never exceeds its budget; a previously-shed request succeeds
   on retry after drain.
3. Router — sticky corpus->host affinity with spillover, against a
   per-host priced-bytes budget vector that placement can never
   breach; fold-cost-weighted tie-breaks.
4. Fleet — N serve subprocesses behind the router serve byte-identical
   artifacts, roll per-host metrics up through the additive histogram
   merge, and SIGTERM-drain to exit 0.
5. stats — `python -m avenir_tpu stats` renders N snapshots (or a
   fleet root) as one merged view.

Every network test binds port 0 (ephemeral) and every subprocess test
polls for observable state — no fixed ports, no bare sleeps.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from avenir_tpu.net.fault import (FaultPolicy, Lease, LeaseStore,
                                  RestartTracker, hot_hosts)
from avenir_tpu.net.fleet import Fleet, FleetError, affinity_key
from avenir_tpu.net.listener import EdgePolicy, NetListener
from avenir_tpu.net.router import AffinityRouter, RouterError
from avenir_tpu.runner import run_job
from avenir_tpu.server import JobRequest, JobServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SUB_ENV = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=os.pathsep.join(
                    p for p in (REPO, os.environ.get("PYTHONPATH"))
                    if p))

MST_CONF = {"mst.model.states": "L,M,H",
            "mst.class.label.field.ord": "1",
            "mst.skip.field.count": "2",
            "mst.class.labels": "T,F"}


# ---------------------------------------------------------------- fixtures
def _seq(tmp_path, rows=300, seed=12, name="seq.csv"):
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


def _req_obj(csv, out, tenant="default", **extra):
    return {"job": "markovStateTransitionModel", "conf": MST_CONF,
            "inputs": [csv], "output": out, "tenant": tenant, **extra}


def _post(url, obj, expect_error=False):
    """(status, row) of one POST; 4xx/5xx surfaced as (code, body)."""
    req = urllib.request.Request(url, data=json.dumps(obj).encode(),
                                 headers={"Content-Type":
                                          "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=240) as resp:
            return resp.status, json.load(resp), dict(resp.headers)
    except urllib.error.HTTPError as exc:
        if not expect_error:
            raise
        body = json.loads(exc.read() or b"{}")
        return exc.code, body, dict(exc.headers)


def _get(url, expect_error=False):
    try:
        with urllib.request.urlopen(url, timeout=240) as resp:
            return resp.status, json.load(resp)
    except urllib.error.HTTPError as exc:
        if not expect_error:
            raise
        return exc.code, json.loads(exc.read() or b"{}")


def _server(tmp_path, **kw):
    kw.setdefault("state_root", str(tmp_path / "srv_state"))
    kw.setdefault("workers", 1)
    return JobServer(**kw)


# ------------------------------------------------------------------ router
def test_router_affinity_spill_and_budget_vector():
    r = AffinityRouter([100, 100])
    a = r.place(("a",), 60)
    b = r.place(("b",), 60)
    assert {a.host, b.host} == {0, 1}        # least-loaded spread
    assert a.kind == b.kind == "miss"
    # sticky: corpus a returns to its host while it fits
    hit = r.place(("a",), 30)
    assert (hit.host, hit.kind) == (a.host, "hit")
    # over the sticky host's vector entry: spill to the other host,
    # sticky mapping unmoved
    spill = r.place(("a",), 35)
    assert (spill.host, spill.kind) == (b.host, "spill")
    # nothing fits: held, never a breach — and a poller's RETRY of the
    # same arrival must not inflate the held stat (transition-only)
    assert r.place(("c",), 50) is None
    assert r.place(("c",), 50, count_held=False) is None
    snap = r.snapshot()
    for h in snap["hosts"]:
        assert h["assigned_bytes"] <= h["budget_bytes"]
        assert h["peak_assigned_bytes"] <= h["budget_bytes"]
    assert snap["stats"]["held"] == 1
    # release returns capacity; the corpus comes home to its warm host
    r.release(spill)
    r.release(hit)
    home = r.place(("a",), 30)
    assert (home.host, home.kind) == (a.host, "hit")
    # a request over EVERY vector entry can never place
    with pytest.raises(RouterError):
        r.place(("z",), 1000)


def test_router_fold_cost_breaks_byte_ties():
    r = AffinityRouter([1000, 1000])
    # equal bytes on both hosts, but host 0 carries measured-expensive
    # pending folds: the tie must break to host 1
    r.assign_to(0, ("w0",), 100, cost_ms=500.0)
    r.assign_to(1, ("w1",), 100, cost_ms=1.0)
    p = r.place(("new",), 100)
    assert p.host == 1
    # hit-rate counts only routed placements, not pinned warmups
    assert r.affinity_hit_rate() == 0.0
    r2 = AffinityRouter([1000])
    r2.place(("k",), 10)
    r2.place(("k",), 10)
    assert r2.affinity_hit_rate() == 0.5


# ------------------------------------------------------------ avenir-fault
def test_restart_tracker_backoff_and_quarantine():
    p = FaultPolicy(restart_backoff_base_s=0.5,
                    restart_backoff_cap_s=4.0, max_restarts=2,
                    quarantine_window_s=60.0)
    t = RestartTracker(p)
    assert t.record_death(0.0) == "restarting"
    assert t.backoff_s() == 0.5
    assert t.record_death(1.0) == "restarting"
    assert t.backoff_s() == 1.0          # capped exponential
    assert t.record_death(2.0) == "quarantined"
    # deaths OUTSIDE the window age out: a host that dies once an hour
    # is restarted every time, never quarantined
    t2 = RestartTracker(p)
    for now in (0.0, 100.0, 200.0, 300.0, 400.0):
        assert t2.record_death(now) == "restarting"
    # ... and the backoff caps
    t3 = RestartTracker(FaultPolicy(restart_backoff_base_s=1.0,
                                    restart_backoff_cap_s=4.0,
                                    max_restarts=100))
    for now in range(6):
        t3.record_death(float(now))
    assert t3.backoff_s() == 4.0


def test_lease_store_roundtrip_renew_expiry(tmp_path):
    store = LeaseStore(str(tmp_path))
    lease = Lease(name="r1.json", host=0, claimed_at=100.0, ttl_s=5.0,
                  hosts=[0], nonce="n1")
    store.write(lease)
    assert store.names() == ["r1.json"]
    back = store.load("r1.json")
    assert (back.host, back.nonce, back.hosts) == (0, "n1", [0])
    assert not back.expired(104.9) and back.expired(105.1)
    store.renew(back, 200.0)
    assert store.load("r1.json").claimed_at == 200.0
    store.remove("r1.json")
    assert store.names() == [] and store.load("r1.json") is None


def test_hot_hosts_hedge_decision():
    p = FaultPolicy(hedge_multiple=4.0, hedge_floor_ms=100.0)
    # symmetric load: nobody is hot
    assert hot_hosts({0: 500.0, 1: 520.0}, {}, p, [0, 1]) == []
    # one straggler past 4x the median (lower middle for 2 hosts)
    assert hot_hosts({0: 5000.0, 1: 200.0}, {}, p, [0, 1]) == [0]
    # the pending-age live lower bound counts with no served p99 yet
    assert hot_hosts({}, {0: 5000.0}, p, [0, 1]) == [0]
    # idle fleet: the floor keeps microscopic wobbles from hedging
    assert hot_hosts({0: 2.0, 1: 0.1}, {}, p, [0, 1]) == []
    # fewer than two healthy hosts: nowhere to mirror
    assert hot_hosts({0: 5000.0, 1: 1.0}, {}, p, [0]) == []
    off = FaultPolicy(hedge=False, hedge_floor_ms=100.0)
    assert hot_hosts({0: 5000.0, 1: 1.0}, {}, off, [0, 1]) == []


def test_router_failover_and_reintegration():
    r = AffinityRouter([100, 100])
    a = r.place(("a",), 10)
    assert a.kind == "miss"
    r.release(a)
    # warm host leaves serving: the sticky mapping DROPS (failover)
    # and the corpus re-places on a serving host
    r.set_host_state(a.host, "restarting")
    b = r.place(("a",), 10)
    assert b.host != a.host and b.kind == "miss"
    assert r.stats["failovers"] == 1
    # reintegration: the recovered host re-EARNS affinity through new
    # placements, never a map reset — corpus a stays with its new home
    r.set_host_state(a.host, "serving")
    c = r.place(("a",), 10)
    assert (c.host, c.kind) == (b.host, "hit")
    # ... and a new corpus lands on the recovered least-loaded host
    d = r.place(("new",), 10)
    assert (d.host, d.kind) == (a.host, "miss")
    # per-request exclusion (the requeue path): never back to a host
    # the request already failed on, sticky mapping unmoved
    e = r.place(("a",), 10, exclude=[b.host])
    assert e.host != b.host and e.kind == "spill"
    # mirrors: least-loaded serving host outside the exclusion set;
    # a quarantined fleet-mate can never take one
    r.set_host_state(a.host, "quarantined")
    assert r.place_mirror(("a",), 10, exclude=[b.host]) is None
    m = r.place_mirror(("a",), 10)
    assert (m.host, m.kind) == (b.host, "hedge")
    assert r.stats["hedges"] == 1
    assert r.snapshot()["hosts"][a.host]["state"] == "quarantined"


def test_fleet_quarantine_and_reinstate(tmp_path, monkeypatch):
    """Supervision policy end to end over stand-in host processes: a
    host that keeps dying is restarted with backoff, quarantined past
    max_restarts, routed around, and re-earns service on operator
    reinstate — all driven through the real _fault_tick."""

    class FakeProc:
        def __init__(self, rc=None):
            self.rc = rc
            self.pid = 4242

        def poll(self):
            return self.rc

    policy = FaultPolicy(poll_interval_s=0.05, max_restarts=1,
                         restart_backoff_base_s=0.0,
                         quarantine_window_s=60.0, hedge=False)
    fleet = Fleet(str(tmp_path / "fleet"), hosts=2,
                  fault_policy=policy)

    def fake_spawn_dying(i):
        with fleet._lock:
            fleet._procs[i] = FakeProc(rc=137)   # dies again instantly
            fleet._spawned_at[i] = time.time()
            fleet._spawned_mono[i] = time.monotonic()

    monkeypatch.setattr(fleet, "_spawn_host", fake_spawn_dying)
    with fleet._lock:
        fleet._procs[0] = FakeProc(rc=137)       # dead on arrival
        fleet._procs[1] = FakeProc()             # healthy
        fleet._spawned_at = [time.time()] * 2
        fleet._spawned_mono = [time.monotonic()] * 2
    fleet._fault_tick()              # death 1 -> restarting
    assert fleet.host_state(0) == "restarting"
    fleet._fault_tick()              # backoff elapsed -> respawn
    assert fleet.fault_snapshot()["stats"]["restarts"] == 1
    fleet._fault_tick()              # death 2 in-window -> quarantine
    assert fleet.host_state(0) == "quarantined"
    assert fleet.router.snapshot()["hosts"][0]["state"] == "quarantined"
    assert fleet.fault_snapshot()["stats"]["quarantined"] == 1
    # placement routes around the quarantined host
    placed = fleet.router.place(("k",), 10)
    assert placed.host == 1
    fleet.router.release(placed)
    # operator reinstate: record cleared, host serves again
    def fake_spawn_ok(i):
        with fleet._lock:
            fleet._procs[i] = FakeProc()
            fleet._spawned_at[i] = time.time()
            fleet._spawned_mono[i] = time.monotonic()

    monkeypatch.setattr(fleet, "_spawn_host", fake_spawn_ok)
    fleet.reinstate(0)
    assert fleet.host_state(0) == "serving"
    with pytest.raises(FleetError):
        fleet.reinstate(1)           # only quarantined hosts reinstate


# ---------------------------------------------------------------- listener
def test_listener_round_trip_byte_identical(tmp_path):
    csv = _seq(tmp_path)
    srv = _server(tmp_path).start()
    with NetListener(srv, port=0) as lis:
        url = f"http://127.0.0.1:{lis.port}"
        # blocking submit
        code, row, _ = _post(url + "/submit?wait=1",
                             _req_obj(csv, str(tmp_path / "net1.txt")))
        assert code == 200 and row["ok"]
        assert row["counters"]["Server:BatchSize"] >= 1.0
        # async submit + result poll
        code, sub, _ = _post(url + "/submit",
                             _req_obj(csv, str(tmp_path / "net2.txt")))
        assert code == 202 and sub["status"] == "queued"
        assert sub["priced_bytes"] > 0
        code, row2 = _get(url + f"/result/{sub['req_id']}?timeout=120")
        assert code == 200 and row2["ok"]
        # fetched results are popped: a second fetch is a 404
        code, _ = _get(url + f"/result/{sub['req_id']}",
                       expect_error=True)
        assert code == 404
        # metrics carries the server snapshot + the edge section + the
        # mergeable raw buckets
        code, snap = _get(url + "/metrics")
        assert code == 200
        assert snap["stats"]["served"] >= 2
        assert snap["edge"]["accepted"] == 2
        assert snap["hists_raw"]["queue_wait_ms"]["count"] >= 2
        code, health = _get(url + "/healthz")
        assert code == 200 and health["status"] == "serving"
        # malformed requests answer 400, not a stack trace
        code, err, _ = _post(url + "/submit",
                             {"job": "noSuchJob", "inputs": [csv],
                              "output": "x"}, expect_error=True)
        assert code == 400 and "KeyError" in err["error"]
        code, err, _ = _post(url + "/submit", {"jobb": "x"},
                             expect_error=True)
        assert code == 400
    srv.shutdown()
    twin = run_job("markovStateTransitionModel", MST_CONF, [csv],
                   str(tmp_path / "net_ref.txt"))
    for out in ("net1.txt", "net2.txt"):
        with open(tmp_path / out, "rb") as fa, \
                open(twin.outputs[0], "rb") as fb:
            assert fa.read() == fb.read()


def test_edge_sheds_flood_and_recovers_after_drain(tmp_path):
    """The load-shed contract: a flood priced over budget gets 429 with
    Retry-After AT THE EDGE, the server's peak priced bytes never
    exceed its budget, and a previously-shed request succeeds on retry
    once in-flight work drains."""
    csv = _seq(tmp_path)
    # deliberately NOT started yet: the first request stays queued, so
    # the flood's 429s below are deterministic — a warm process can
    # otherwise serve the first request between two POSTs and free the
    # edge capacity the flood was meant to breach
    srv = _server(tmp_path, budget_bytes=150 << 20,
                  pricer=lambda reqs, reserve: (100 << 20) * len(reqs),
                  rss_probe=lambda: 0)
    with NetListener(srv, port=0) as lis:
        url = f"http://127.0.0.1:{lis.port}"
        code, first, _ = _post(url + "/submit",
                               _req_obj(csv, str(tmp_path / "s0.txt")))
        assert code == 202
        shed = 0
        for i in range(4):
            code, err, headers = _post(
                url + "/submit",
                _req_obj(csv, str(tmp_path / f"sf{i}.txt"),
                         tenant=f"t{i}"),
                expect_error=True)
            assert code == 429
            assert int(headers["Retry-After"]) >= 1
            assert "budget" in err["error"]
            shed += 1
        assert shed == 4
        # the server starts, the in-flight request finishes, the edge
        # frees its priced bytes — the SAME previously-shed request
        # now succeeds
        srv.start()
        code, row = _get(url + f"/result/{first['req_id']}?timeout=240")
        assert code == 200 and row["ok"]
        deadline = time.perf_counter() + 30
        while True:
            code, retried, _ = _post(
                url + "/submit?wait=1",
                _req_obj(csv, str(tmp_path / "sf0.txt"), tenant="t0"),
                expect_error=True)
            if code == 200:
                break
            assert code == 429
            assert time.perf_counter() < deadline, \
                "shed request never recovered after drain"
            time.sleep(0.1)
        assert retried["ok"]
        edge = lis.edge_stats()
        assert edge["rejected"] >= 4
    stats = srv.stats()
    srv.shutdown()
    assert stats["peak_priced_bytes"] <= 150 << 20


def test_edge_hold_mode_parks_instead_of_429(tmp_path):
    csv = _seq(tmp_path)
    srv = _server(tmp_path, budget_bytes=150 << 20,
                  pricer=lambda reqs, reserve: (100 << 20) * len(reqs),
                  rss_probe=lambda: 0).start()
    policy = EdgePolicy(shed_mode="hold", hold_timeout_s=120.0)
    with NetListener(srv, port=0, policy=policy) as lis:
        url = f"http://127.0.0.1:{lis.port}"
        code, _first, _ = _post(url + "/submit",
                                _req_obj(csv, str(tmp_path / "h0.txt")))
        assert code == 202
        # over budget: the edge PARKS the accept until the first
        # request frees its priced bytes, then serves — never a 429
        code, row, _ = _post(url + "/submit?wait=1",
                             _req_obj(csv, str(tmp_path / "h1.txt"),
                                      tenant="b"))
        assert code == 200 and row["ok"]
        edge = lis.edge_stats()
        assert edge["rejected"] == 0
        assert edge["held_accepts"] >= 1
    srv.shutdown()


def test_edge_tenant_depth_bound(tmp_path):
    csv = _seq(tmp_path)
    srv = _server(tmp_path)          # deliberately NOT started: queued
    policy = EdgePolicy(max_tenant_depth=2)
    with NetListener(srv, port=0, policy=policy) as lis:
        url = f"http://127.0.0.1:{lis.port}"
        for i in range(2):
            code, _row, _ = _post(
                url + "/submit",
                _req_obj(csv, str(tmp_path / f"d{i}.txt"), tenant="t"))
            assert code == 202
        code, err, headers = _post(
            url + "/submit",
            _req_obj(csv, str(tmp_path / "d2.txt"), tenant="t"),
            expect_error=True)
        assert code == 429 and "depth" in err["error"]
        assert "Retry-After" in headers
        # another tenant is NOT shed by t's depth
        code, _row, _ = _post(
            url + "/submit",
            _req_obj(csv, str(tmp_path / "d3.txt"), tenant="u"))
        assert code == 202
        srv.start()
        srv.drain(timeout=240)
    srv.shutdown()


def test_edge_reused_req_id_does_not_leak_budget(tmp_path):
    """A client retrying with the SAME req_id while the first attempt
    is in flight must not ratchet the edge's outstanding total up —
    the replaced entry's priced bytes are freed on re-register."""
    csv = _seq(tmp_path)
    srv = _server(tmp_path, budget_bytes=250 << 20,
                  pricer=lambda reqs, reserve: (100 << 20) * len(reqs),
                  rss_probe=lambda: 0)   # not started: all stay queued
    with NetListener(srv, port=0) as lis:
        url = f"http://127.0.0.1:{lis.port}"
        for attempt in range(2):         # same req_id twice
            code, _row, _ = _post(url + "/submit", _req_obj(
                csv, str(tmp_path / f"rr_{attempt}.txt"),
                req_id="fixed-id"))
            assert code == 202
        # outstanding must be ONE 100MB entry, so a third distinct
        # request (100MB) still fits the 250MB edge budget
        assert lis.edge_stats()["outstanding_priced_bytes"] == 100 << 20
        code, _row, _ = _post(url + "/submit",
                              _req_obj(csv, str(tmp_path / "rr2.txt"),
                                       tenant="u"))
        assert code == 202
        srv.start()
        srv.drain(timeout=240)
    srv.shutdown()


def test_edge_unfetched_results_expire(tmp_path):
    """Fire-and-forget clients must not grow a resident edge forever:
    a served-but-never-fetched result is dropped after result_ttl_s."""
    csv = _seq(tmp_path)
    srv = _server(tmp_path).start()
    policy = EdgePolicy(result_ttl_s=0.2)
    with NetListener(srv, port=0, policy=policy) as lis:
        url = f"http://127.0.0.1:{lis.port}"
        code, sub, _ = _post(url + "/submit",
                             _req_obj(csv, str(tmp_path / "ttl.txt")))
        assert code == 202
        srv.drain(timeout=240)
        _wait_for(lambda: lis.edge_stats()["outstanding_requests"] == 0,
                  30, "unfetched result expired")
        code, _ = _get(url + f"/result/{sub['req_id']}",
                       expect_error=True)
        assert code == 404
    srv.shutdown()


def test_edge_malformed_timeout_is_400_not_crash(tmp_path):
    csv = _seq(tmp_path)
    srv = _server(tmp_path).start()
    with NetListener(srv, port=0) as lis:
        url = f"http://127.0.0.1:{lis.port}"
        code, err = _get(url + "/result/whatever?timeout=abc",
                         expect_error=True)
        assert code == 400 and "timeout" in err["error"]
        code, _err, _ = _post(url + "/submit?wait=1&timeout=nope",
                              _req_obj(csv, str(tmp_path / "tq.txt")),
                              expect_error=True)
        assert code == 400
        srv.drain(timeout=240)           # the 400'd job still ran
    srv.shutdown()


def test_edge_policy_not_mutated_across_listeners(tmp_path):
    """Resolving the default edge budget must never write through to a
    caller's shared EdgePolicy — listener B would inherit listener A's
    server budget and accept work B's admission can never hold."""
    policy = EdgePolicy(shed_mode="hold")
    srv_a = _server(tmp_path, budget_bytes=3 << 30)
    srv_b = JobServer(budget_bytes=150 << 20,
                      state_root=str(tmp_path / "b_state"))
    lis_a = NetListener(srv_a, port=0, policy=policy)
    lis_b = NetListener(srv_b, port=0, policy=policy)
    try:
        assert policy.budget_bytes is None       # caller's object intact
        assert lis_a.policy.budget_bytes == 3 << 30
        assert lis_b.policy.budget_bytes == 150 << 20
        assert lis_b.policy.shed_mode == "hold"  # knobs still copied
    finally:
        # never started: close the bound sockets directly (stop() joins
        # an accept loop these listeners never ran)
        lis_a._httpd.server_close()
        lis_b._httpd.server_close()
        srv_a.shutdown(drain=False)
        srv_b.shutdown(drain=False)


def test_listener_retry_after_jitter(tmp_path):
    """Shed responses carry a ±20%-jittered Retry-After so a cohort of
    synchronized shed clients does not retry in lockstep and
    re-stampede the edge at one instant."""
    csv = _seq(tmp_path)
    srv = _server(tmp_path, budget_bytes=150 << 20,
                  pricer=lambda reqs, reserve: (100 << 20) * len(reqs),
                  rss_probe=lambda: 0)     # not started: first queues
    policy = EdgePolicy(retry_after_s=10.0)
    with NetListener(srv, port=0, policy=policy) as lis:
        url = f"http://127.0.0.1:{lis.port}"
        code, _row, _ = _post(url + "/submit",
                              _req_obj(csv, str(tmp_path / "j0.txt")))
        assert code == 202
        hints = []
        for i in range(12):
            code, err, headers = _post(
                url + "/submit",
                _req_obj(csv, str(tmp_path / f"j{i}.txt"),
                         tenant=f"t{i}"),
                expect_error=True)
            assert code == 429
            hint = err["retry_after_s"]
            assert 8.0 <= hint <= 12.0        # ±20% of the 10s policy
            assert int(headers["Retry-After"]) >= 8
            hints.append(hint)
        assert min(hints) < max(hints)        # jittered, not lockstep
    srv.shutdown(drain=False)


def test_listener_healthz_supervision_states(tmp_path):
    """/healthz surfaces the supervision overlay: quarantined and
    restarting answer 503 with the state in-band (and refuse new
    submissions the same way draining does); clearing the overlay
    returns the edge to serving."""
    csv = _seq(tmp_path)
    srv = _server(tmp_path).start()
    with NetListener(srv, port=0) as lis:
        url = f"http://127.0.0.1:{lis.port}"
        code, health = _get(url + "/healthz")
        assert code == 200 and health["status"] == "serving"
        for state in ("quarantined", "restarting"):
            lis.set_health_state(state)
            code, health = _get(url + "/healthz", expect_error=True)
            assert code == 503 and health["status"] == state
            code, err, _ = _post(url + "/submit",
                                 _req_obj(csv, str(tmp_path / "hs.txt")),
                                 expect_error=True)
            assert code == 503 and err["status"] == state
        with pytest.raises(ValueError):
            lis.set_health_state("weird")
        lis.set_health_state(None)
        code, row, _ = _post(url + "/submit?wait=1",
                             _req_obj(csv, str(tmp_path / "hs2.txt")))
        assert code == 200 and row["ok"]
        assert lis.edge_stats()["health_state"] == "serving"
    srv.shutdown()


def test_listener_drain_state(tmp_path):
    csv = _seq(tmp_path)
    srv = _server(tmp_path).start()
    with NetListener(srv, port=0) as lis:
        url = f"http://127.0.0.1:{lis.port}"
        code, _row, _ = _post(url + "/submit?wait=1",
                              _req_obj(csv, str(tmp_path / "dr.txt")))
        assert code == 200
        lis.begin_drain()
        code, health = _get(url + "/healthz", expect_error=True)
        assert code == 503 and health["status"] == "draining"
        code, err, _ = _post(url + "/submit",
                             _req_obj(csv, str(tmp_path / "dr2.txt")),
                             expect_error=True)
        assert code == 503 and err["status"] == "draining"
    srv.shutdown()


# ------------------------------------------------------------- subprocesses
def _wait_for(predicate, timeout, what):
    deadline = time.perf_counter() + timeout
    while not predicate():
        assert time.perf_counter() < deadline, f"timed out: {what}"
        time.sleep(0.05)


def test_serve_spool_sigterm_graceful_drain(tmp_path):
    """SIGTERM on a `serve --spool` session is a graceful drain: the
    claimed request finishes, the final metrics.json lands, exit 0."""
    csv = _seq(tmp_path)
    spool = str(tmp_path / "spool")
    os.makedirs(os.path.join(spool, "in"), exist_ok=True)
    proc = subprocess.Popen(
        [sys.executable, "-m", "avenir_tpu", "serve", "--spool", spool,
         "--workers", "1", "--metrics-interval", "0.2"],
        cwd=REPO, env=_SUB_ENV, stderr=subprocess.PIPE, text=True)
    try:
        req = _req_obj(csv, str(tmp_path / "sig.txt"))
        tmp = os.path.join(spool, "r1.json.tmp")
        with open(tmp, "w") as fh:
            json.dump(req, fh)
        os.replace(tmp, os.path.join(spool, "in", "r1.json"))
        out_path = os.path.join(spool, "out", "r1.json")
        _wait_for(lambda: os.path.exists(out_path), 240,
                  "spooled request served")
        proc.send_signal(signal.SIGTERM)
        _stdout, stderr = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode == 0, stderr[-800:]
    assert '"drained": true' in stderr
    with open(os.path.join(spool, "metrics.json")) as fh:
        snap = json.load(fh)
    assert snap["stats"]["served"] >= 1
    with open(out_path) as fh:
        assert json.load(fh)["ok"]


def test_serve_listen_cli_sigterm(tmp_path):
    """`serve --listen 127.0.0.1:0`: ephemeral port via --port-file,
    HTTP round trip, SIGTERM drains to exit 0."""
    csv = _seq(tmp_path)
    port_file = str(tmp_path / "port")
    proc = subprocess.Popen(
        [sys.executable, "-m", "avenir_tpu", "serve", "--listen",
         "127.0.0.1:0", "--workers", "1", "--port-file", port_file],
        cwd=REPO, env=_SUB_ENV, stderr=subprocess.PIPE, text=True)
    try:
        _wait_for(lambda: os.path.exists(port_file), 120, "port file")
        with open(port_file) as fh:
            port = int(fh.read())
        url = f"http://127.0.0.1:{port}"
        code, row, _ = _post(url + "/submit?wait=1",
                             _req_obj(csv, str(tmp_path / "lc.txt")))
        assert code == 200 and row["ok"]
        code, health = _get(url + "/healthz")
        assert code == 200
        proc.send_signal(signal.SIGTERM)
        _stdout, stderr = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode == 0, stderr[-800:]
    twin = run_job("markovStateTransitionModel", MST_CONF, [csv],
                   str(tmp_path / "lc_ref.txt"))
    with open(tmp_path / "lc.txt", "rb") as fa, \
            open(twin.outputs[0], "rb") as fb:
        assert fa.read() == fb.read()


def test_fleet_two_hosts_round_trip(tmp_path):
    """2 subprocess hosts behind the router: byte-identical artifacts,
    corpus affinity (repeats hit the warm host), per-host metrics
    merged through the additive histogram algebra, SIGTERM exit 0."""
    a = _seq(tmp_path, seed=1, name="a.csv")
    b = _seq(tmp_path, seed=2, name="b.csv")
    # quiet fault policy: this is the ROUND-TRIP test, and its placed/
    # hit-rate assertions are exact — on a starved CI box the default
    # 10s lease TTL / hedging can fire mid-trip and legitimately add
    # placements (their own tests cover that); park them out of reach
    fleet = Fleet(str(tmp_path / "fleet"), hosts=2, workers=1,
                  env=_SUB_ENV,
                  fault_policy=FaultPolicy(lease_ttl_s=3600.0,
                                           heartbeat_timeout_s=3600.0,
                                           hedge=False))
    fleet.start()
    try:
        names = {}
        for i, corpus in enumerate([a, b, a, b]):
            names[i] = fleet.submit(_req_obj(
                corpus, str(tmp_path / f"fo{i}.txt"), tenant=f"t{i}"))
        rows = fleet.collect(list(names.values()), timeout=240)
        assert all(r["ok"] for r in rows.values())
        snap = fleet.merged_metrics()
        router = fleet.router.snapshot()
    finally:
        codes = fleet.stop()
    assert codes == [0, 0]             # SIGTERM drained both hosts
    assert snap["hosts"] == 2
    # 4 placements over 2 corpora: 2 misses seed the map, 2 repeats hit
    assert router["stats"]["affinity_misses"] == 2
    assert router["stats"]["affinity_hits"] == 2
    assert fleet.router.affinity_hit_rate() == 0.5
    for h in router["hosts"]:
        assert h["peak_assigned_bytes"] <= h["budget_bytes"]
    # the final fleet metrics.json was written by stop() from the
    # hosts' shutdown snapshots — the deterministic place to assert the
    # merged counters and the additive histogram fold (the live `snap`
    # depends on interval timing)
    with open(tmp_path / "fleet" / "metrics.json") as fh:
        final = json.load(fh)
    assert final["stats"]["served"] >= 4.0
    assert final["router"]["stats"]["placed"] == 4
    # merged hists fold both hosts' queue-wait distributions
    assert final["hists"]["queue_wait_ms"]["count"] >= 4
    twins = {
        a: run_job("markovStateTransitionModel", MST_CONF, [a],
                   str(tmp_path / "fa_ref.txt")),
        b: run_job("markovStateTransitionModel", MST_CONF, [b],
                   str(tmp_path / "fb_ref.txt")),
    }
    for i, corpus in enumerate([a, b, a, b]):
        with open(tmp_path / f"fo{i}.txt", "rb") as fa, \
                open(twins[corpus].outputs[0], "rb") as fb:
            assert fa.read() == fb.read()


def test_fleet_blocking_submit_sweeps_its_own_capacity(tmp_path):
    """A saturated single-threaded front must not livelock: a blocking
    submit sweeps finished results itself to free the budget vector,
    and the banked rows still arrive through their named collect."""
    csv = _seq(tmp_path)
    probe = Fleet(str(tmp_path / "probe"), hosts=1, env=_SUB_ENV)
    _req, priced, _cost = probe.price(_req_obj(csv, "x"))
    # budget fits exactly ONE request at a time
    fleet = Fleet(str(tmp_path / "fleet"), hosts=1,
                  budget_mb=priced * 1.5 / (1 << 20), env=_SUB_ENV)
    fleet.start()
    try:
        names = [fleet.submit(_req_obj(csv, str(tmp_path / f"sw{i}.txt"),
                                       tenant=f"t{i}"), timeout=240)
                 for i in range(3)]      # 2nd/3rd block until a sweep
        rows = fleet.collect(names, timeout=240)
    finally:
        codes = fleet.stop()
    assert codes == [0]
    assert sorted(rows) == sorted(names)
    assert all(r["ok"] for r in rows.values())
    snap = fleet.router.snapshot()
    assert snap["hosts"][0]["peak_assigned_bytes"] <= \
        snap["hosts"][0]["budget_bytes"]
    assert snap["hosts"][0]["assigned_bytes"] == 0   # all released


def test_fleet_hosts_keep_their_admission_peak_inside_the_budget_vector(
        tmp_path):
    """The budget vector holds on both sides of the spool: the router
    never assigns a host past its entry, and no host's own admission
    ever prices more in flight than that entry, by the host's own
    account (its last `metrics.json`). The entry fits one request."""
    a = _seq(tmp_path, seed=1, name="a.csv")
    b = _seq(tmp_path, seed=2, name="b.csv")
    probe = Fleet(str(tmp_path / "probe"), hosts=1, env=_SUB_ENV)
    _req, priced, _cost = probe.price(_req_obj(a, "x"))
    budget_mb = priced * 1.5 / (1 << 20)
    fleet = Fleet(str(tmp_path / "fleet"), hosts=2, workers=1,
                  budget_mb=budget_mb, env=_SUB_ENV,
                  fault_policy=FaultPolicy(lease_ttl_s=3600.0,
                                           heartbeat_timeout_s=3600.0,
                                           hedge=False))
    fleet.start()
    try:
        names = [fleet.submit(_req_obj(corpus,
                                       str(tmp_path / f"bv{i}.txt"),
                                       tenant=f"t{i}"), timeout=240)
                 for i, corpus in enumerate([a, b, a, b])]
        rows = fleet.collect(names, timeout=240)
        router = fleet.router.snapshot()
    finally:
        codes = fleet.stop()
    assert codes == [0, 0]
    assert all(r["ok"] for r in rows.values())
    for h in router["hosts"]:
        assert h["peak_assigned_bytes"] <= h["budget_bytes"]
    for i in range(2):
        with open(tmp_path / "fleet" / f"host{i}" / "metrics.json") as fh:
            inflight = json.load(fh)["inflight"]
        assert inflight["budget_bytes"] == int(budget_mb * (1 << 20))
        assert 0 < inflight["peak_priced_bytes"] <= inflight["budget_bytes"]


def test_fleet_cli_once(tmp_path):
    """`python -m avenir_tpu fleet --root R --hosts 1 --once`: requests
    spooled into the FLEET root are routed, served, and answered in
    <root>/out with nonce namespacing; merged metrics land at the
    root."""
    csv = _seq(tmp_path)
    root = str(tmp_path / "froot")
    os.makedirs(os.path.join(root, "in"), exist_ok=True)
    drops = [("q1.json", _req_obj(csv, str(tmp_path / "fc.txt"),
                                  nonce="client7")),
             ("q2.json", {"job": "noSuchJob", "conf": {},
                          "inputs": [csv], "output": "x",
                          "nonce": "bad1"})]
    for name, req in drops:
        tmp = os.path.join(root, f"{name}.tmp")
        with open(tmp, "w") as fh:
            json.dump(req, fh)
        os.replace(tmp, os.path.join(root, "in", name))
    proc = subprocess.run(
        [sys.executable, "-m", "avenir_tpu", "fleet", "--root", root,
         "--hosts", "1", "--once", "--metrics-interval", "0.2"],
        cwd=REPO, env=_SUB_ENV, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 1, proc.stderr[-800:]   # 1 failed request
    with open(os.path.join(root, "out", "client7.q1.json")) as fh:
        row = json.load(fh)
    assert row["ok"] and row["nonce"] == "client7"
    # the FAILED request's row honors its nonce namespace too
    with open(os.path.join(root, "out", "bad1.q2.json")) as fh:
        bad = json.load(fh)
    assert not bad["ok"] and bad["nonce"] == "bad1"
    assert "noSuchJob" in bad["error"]
    with open(os.path.join(root, "metrics.json")) as fh:
        snap = json.load(fh)
    assert snap["router"]["stats"]["placed"] == 1
    # `stats` on a 1-host fleet root still renders the router section
    from avenir_tpu.obs.report import stats_main

    assert stats_main([root]) == 0


def test_serve_stdin_still_killed_by_sigterm(tmp_path):
    """--stdin sessions keep the DEFAULT signal semantics (EOF is
    their graceful end): SIGTERM must terminate the process, not be
    absorbed by a drain handler nothing in the stdin path reads."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "avenir_tpu", "serve", "--stdin",
         "--workers", "1"],
        cwd=REPO, env=_SUB_ENV, stdin=subprocess.PIPE,
        stderr=subprocess.DEVNULL)
    try:
        time.sleep(1.0)                  # let it reach the read loop
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert rc != 0                       # killed by the signal, not hung


def test_spool_failure_row_keeps_nonce(tmp_path):
    """A nonce-carrying request that FAILS (unknown job) still writes
    its row at out/<nonce>.<name> — the polling client must see the
    failure, and the un-namespaced stem must stay unclobbered."""
    import threading

    from avenir_tpu.server.spool import serve_spool

    spool = str(tmp_path / "spool")
    os.makedirs(os.path.join(spool, "in"), exist_ok=True)
    stop = threading.Event()
    srv = _server(tmp_path)
    with srv:
        t = threading.Thread(target=lambda: serve_spool(
            srv, spool, should_stop=stop.is_set))
        t.start()
        try:
            req = {"job": "noSuchJob", "conf": {}, "inputs": [],
                   "output": "x", "nonce": "cfail"}
            tmp = os.path.join(spool, "bad.tmp")
            with open(tmp, "w") as fh:
                json.dump(req, fh)
            os.replace(tmp, os.path.join(spool, "in", "bad.json"))
            out = os.path.join(spool, "out", "cfail.bad.json")
            _wait_for(lambda: os.path.exists(out), 60,
                      "nonce-namespaced failure row")
        finally:
            stop.set()
            t.join(30)
        assert not t.is_alive()
    with open(out) as fh:
        row = json.load(fh)
    assert not row["ok"] and row["nonce"] == "cfail"
    assert "noSuchJob" in row["error"]


def test_spool_dead_letters_torn_request(tmp_path):
    """A truncated request JSON leaves the claim loop FOR GOOD: moved
    to <spool>/dead/ with a reason file (the crash-loop fix), the
    in-band failure row still written, and the session keeps serving
    the next request."""
    import threading

    from avenir_tpu.server.spool import serve_spool

    csv = _seq(tmp_path)
    spool = str(tmp_path / "spool")
    os.makedirs(os.path.join(spool, "in"), exist_ok=True)
    stop = threading.Event()
    srv = _server(tmp_path)
    with srv:
        t = threading.Thread(target=lambda: serve_spool(
            srv, spool, should_stop=stop.is_set))
        t.start()
        try:
            tmp = os.path.join(spool, "torn.tmp")
            with open(tmp, "w") as fh:     # truncated mid-object
                fh.write('{"job": "markovStateTransitionModel", "inp')
            os.replace(tmp, os.path.join(spool, "in", "torn.json"))
            out = os.path.join(spool, "out", "torn.json")
            _wait_for(lambda: os.path.exists(out), 60,
                      "failure row for the torn request")
            dead_dir = os.path.join(spool, "dead")
            dead = [n for n in os.listdir(dead_dir)
                    if n.startswith("torn.json")
                    and not n.endswith(".reason")]
            assert len(dead) == 1
            with open(os.path.join(dead_dir, dead[0])) as fh:
                assert fh.read().startswith('{"job"')  # bytes preserved
            with open(os.path.join(dead_dir, "torn.json.reason")) as fh:
                assert "JSONDecodeError" in fh.read()
            # never re-claimable: nothing left in work/ or in/
            assert not os.listdir(os.path.join(spool, "work"))
            assert not os.listdir(os.path.join(spool, "in"))
            # the loop survived: a well-formed request still serves
            good = _req_obj(csv, str(tmp_path / "after.txt"))
            tmp2 = os.path.join(spool, "good.tmp")
            with open(tmp2, "w") as fh:
                json.dump(good, fh)
            os.replace(tmp2, os.path.join(spool, "in", "good.json"))
            good_out = os.path.join(spool, "out", "good.json")
            _wait_for(lambda: os.path.exists(good_out), 240,
                      "request served after the dead-letter")
        finally:
            stop.set()
            t.join(30)
        assert not t.is_alive()
    with open(out) as fh:
        row = json.load(fh)
    assert not row["ok"] and "JSONDecodeError" in row["error"]
    with open(good_out) as fh:
        assert json.load(fh)["ok"]


def test_fleet_survives_host_sigkill(tmp_path):
    """The chaos contract at test scale: SIGKILL one host right after
    its requests were placed; supervision detects the death, requeues
    the stranded leases to the healthy host (zero lost), restarts the
    dead host, and every row is byte-identical to its solo twin (zero
    conflicting)."""
    a = _seq(tmp_path, seed=1, name="a.csv")
    b = _seq(tmp_path, seed=2, name="b.csv")
    policy = FaultPolicy(poll_interval_s=0.1, lease_ttl_s=1.0,
                         restart_backoff_base_s=0.2,
                         heartbeat_timeout_s=60.0, hedge=False)
    fleet = Fleet(str(tmp_path / "fleet"), hosts=2, workers=1,
                  env=_SUB_ENV, fault_policy=policy)
    fleet.start()
    try:
        names = {}
        for i, corpus in enumerate([a, b, a, b]):
            names[i] = fleet.submit(_req_obj(
                corpus, str(tmp_path / f"ck{i}.txt"), tenant=f"t{i}"))
        # corpus a's sticky host is 0 (first miss on an idle fleet)
        os.kill(fleet.host_pid(0), signal.SIGKILL)
        rows = fleet.collect(list(names.values()), timeout=240)
        assert all(r["ok"] for r in rows.values())
        snap = fleet.fault_snapshot()
        assert snap["stats"]["requeues"] >= 1       # leases swept over
        assert snap["leases_outstanding"] == 0      # ... and released
        _wait_for(lambda: fleet.fault_snapshot()["stats"]["restarts"]
                  >= 1 and fleet.host_state(0) == "serving", 120,
                  "killed host restarted and reintegrated")
    finally:
        codes = fleet.stop()
    # the surviving host drained gracefully; the restarted one may
    # still have been mid-boot when the TERM landed
    assert codes[1] == 0
    twins = {
        a: run_job("markovStateTransitionModel", MST_CONF, [a],
                   str(tmp_path / "cka_ref.txt")),
        b: run_job("markovStateTransitionModel", MST_CONF, [b],
                   str(tmp_path / "ckb_ref.txt")),
    }
    for i, corpus in enumerate([a, b, a, b]):
        with open(tmp_path / f"ck{i}.txt", "rb") as fa, \
                open(twins[corpus].outputs[0], "rb") as fb:
            assert fa.read() == fb.read()


def test_fleet_hedges_stalled_host(tmp_path):
    """Hedged tail dispatch: a SIGSTOPped host's queued request is
    mirrored to the least-loaded healthy host once its pending age
    blows past the fleet median, and the FIRST result wins — the fleet
    answers while the stalled original never finishes. After SIGCONT
    the late duplicate is an identical write, never a conflict."""
    csv = _seq(tmp_path)
    policy = FaultPolicy(poll_interval_s=0.1, hedge_multiple=2.0,
                         hedge_floor_ms=300.0, lease_ttl_s=3600.0,
                         heartbeat_timeout_s=3600.0)
    fleet = Fleet(str(tmp_path / "fleet"), hosts=2, workers=1,
                  env=_SUB_ENV, fault_policy=policy)
    fleet.start()
    try:
        # warm both hosts: each needs a MEASURED served tail (the
        # hedge gate) and resident compiles
        warm = [fleet.submit_to(h, _req_obj(
            csv, str(tmp_path / f"wh{h}.txt"))) for h in (0, 1)]
        fleet.collect(warm, timeout=240)
        # the hedge gate reads the SERVED tail from each host's
        # heartbeat snapshot: let both heartbeats catch up with the
        # warmups before freezing one (a stopped host can never
        # refresh its own)
        _wait_for(lambda: all(n >= 1 for _p, n in
                              fleet._rolled_p99().values()), 60,
                  "host heartbeats reflect the served warmups")
        os.kill(fleet.host_pid(0), signal.SIGSTOP)
        try:
            # fresh corpus on an idle fleet routes to host 0 — which
            # is stopped and will never serve it
            name = fleet.submit(_req_obj(csv, str(tmp_path / "hg.txt"),
                                         tenant="hg"))
            rows = fleet.collect([name], timeout=240)
            assert rows[name]["ok"]
            assert fleet.router.stats["hedges"] >= 1
            # the stall never looked like a death: no requeue, no
            # restart — hedging alone carried the tail
            snap = fleet.fault_snapshot()
            assert snap["stats"]["requeues"] == 0
            assert snap["stats"]["restarts"] == 0
            assert fleet.host_state(0) == "serving"
        finally:
            os.kill(fleet.host_pid(0), signal.SIGCONT)
    finally:
        codes = fleet.stop()
    assert codes == [0, 0]        # SIGCONT'd host drained gracefully
    twin = run_job("markovStateTransitionModel", MST_CONF, [csv],
                   str(tmp_path / "hg_ref.txt"))
    # byte-identical even though BOTH copies may have run (the resumed
    # original rewrites the same bytes — zero conflicting results)
    with open(tmp_path / "hg.txt", "rb") as fa, \
            open(twin.outputs[0], "rb") as fb:
        assert fa.read() == fb.read()


def test_stranded_lease_after_restart_respools(tmp_path):
    """The restart gap: a claim taken by a DEAD incarnation sits in
    its old work/ dir, which the restarted host never re-adopts. The
    lease sweep must detect a lease predating the current incarnation
    — and with no other host to requeue to, re-spool the request into
    the restarted host's own in/, riding the original budget charge
    (released exactly once when the result lands)."""

    class FakeProc:
        pid = 4242

        def poll(self):
            return None

    csv = _seq(tmp_path, rows=50)
    fleet = Fleet(str(tmp_path / "fleet"), hosts=1,
                  fault_policy=FaultPolicy(hedge=False))
    for sub in ("in", "out", "work"):
        os.makedirs(os.path.join(fleet.host_dirs[0], sub),
                    exist_ok=True)
    with fleet._lock:
        fleet._procs[0] = FakeProc()
    obj = _req_obj(csv, str(tmp_path / "st.txt"))
    req, priced, cost = fleet.price(obj)
    placement = fleet.router.assign_to(0, affinity_key(req), priced,
                                       cost)
    name = fleet._spool_to(placement, obj)
    entry = fleet._outstanding[name]
    spool_file = os.path.join(fleet.host_dirs[0], "in",
                              entry.copies[0].name)
    # restart happened AFTER the lease was claimed, but the spooled
    # file still sits in in/: the new incarnation will claim it, so
    # the sweep restamps instead of moving the request
    with fleet._lock:
        fleet._spawned_at[0] = entry.lease.claimed_at + 10.0
    fleet._sweep_leases(time.time() + 20.0)
    assert fleet.fault_snapshot()["stats"]["respools"] == 0
    assert os.path.exists(spool_file)
    # now the claim is GONE from in/ (the dead incarnation took it to
    # its grave): the sweep must re-spool — requeueing is impossible,
    # every other host is on the lease's exclusion trail
    os.remove(spool_file)
    with fleet._lock:
        fleet._spawned_at[0] = time.time() + 100.0
    fleet._sweep_leases(time.time() + 200.0)
    snap = fleet.fault_snapshot()
    assert snap["stats"]["respools"] == 1
    assert snap["stats"]["requeues"] == 0
    new_copy = fleet._outstanding[name].copies[-1]
    assert os.path.exists(os.path.join(fleet.host_dirs[0], "in",
                                       new_copy.name))
    # a row landing on the re-spooled copy completes the request and
    # releases the SINGLE shared budget charge exactly once
    with open(new_copy.out_path + ".tmp", "w") as fh:
        json.dump({"ok": True}, fh)
    os.replace(new_copy.out_path + ".tmp", new_copy.out_path)
    rows = fleet.collect([name], timeout=30)
    assert rows[name]["ok"]
    host = fleet.router.snapshot()["hosts"][0]
    assert host["assigned_bytes"] == 0
    assert host["assigned_requests"] == 0
    assert fleet.fault_snapshot()["leases_outstanding"] == 0


def _stranded_two_host_fleet(tmp_path, alive):
    """A 2-host fleet with stand-in processes and ONE outstanding
    request whose attempt trail already covers both hosts, lease held
    by host 1. ``alive`` flags which hosts have a live process."""

    class FakeProc:
        pid = 4242

        def poll(self):
            return None

    csv = _seq(tmp_path, rows=50)
    fleet = Fleet(str(tmp_path / "fleet"), hosts=2,
                  fault_policy=FaultPolicy(hedge=False))
    for h in range(2):
        for sub in ("in", "out", "work"):
            os.makedirs(os.path.join(fleet.host_dirs[h], sub),
                        exist_ok=True)
    with fleet._lock:
        fleet._procs = [FakeProc() if alive[h] else None
                        for h in range(2)]
        fleet._spawned_at = [time.time() - 1.0] * 2
        fleet._spawned_mono = [time.monotonic() - 1.0] * 2
    obj = _req_obj(csv, str(tmp_path / "stranded.txt"))
    req, priced, cost = fleet.price(obj)
    name = fleet._spool_to(
        fleet.router.assign_to(0, affinity_key(req), priced, cost), obj)
    entry = fleet._outstanding[name]
    # simulate the earlier requeue that put host 1 on the trail: a
    # second copy spooled at host 1, lease moved there
    copy = fleet._write_copy(
        fleet.router.assign_to(1, affinity_key(req), priced, cost),
        fleet._next_name(), obj)
    entry.copies.append(copy)
    entry.lease.host = 1
    entry.lease.hosts = [0, 1]
    fleet._leases.write(entry.lease)
    return fleet, name, entry


def test_stranded_request_respools_to_healthy_trail_host(tmp_path):
    """The stranded-request hang: a request whose attempt trail covers
    EVERY host can neither requeue (all hosts excluded) nor pass the
    max_requeues cap (attempts only grows on successful moves) when
    its lease host is dead — it used to sit until the collect()
    timeout. The sweep must respool it to a healthy trail host
    in-band: re-execution is safe by the idempotency contract."""
    from avenir_tpu.net import fault

    fleet, name, entry = _stranded_two_host_fleet(
        tmp_path, alive=[True, False])
    with fleet._lock:
        fleet._host_state[1] = fault.RESTARTING   # host 1 died
    fleet._sweep_leases(time.time())
    snap = fleet.fault_snapshot()
    assert snap["stats"]["respools"] == 1
    assert snap["stats"]["requeues"] == 0
    assert snap["stats"]["abandoned"] == 0
    assert entry.lease.host == 0        # moved to the healthy trail host
    new_copy = entry.copies[-1]
    assert new_copy.placement.host == 0
    assert os.path.exists(os.path.join(fleet.host_dirs[0], "in",
                                       new_copy.name))
    # a row on the respooled copy completes the request; the shared
    # budget charges release exactly once each
    with open(new_copy.out_path + ".tmp", "w") as fh:
        json.dump({"ok": True}, fh)
    os.replace(new_copy.out_path + ".tmp", new_copy.out_path)
    rows = fleet.collect([name], timeout=30)
    assert rows[name]["ok"]
    for h in range(2):
        host = fleet.router.snapshot()["hosts"][h]
        assert host["assigned_bytes"] == 0
    assert fleet.fault_snapshot()["leases_outstanding"] == 0


def test_stranded_request_abandons_in_band_when_no_host_left(tmp_path):
    """Same trail-exhausted shape, but NO healthy host remains (lease
    host dead, the other quarantined): the request must resolve as an
    in-band failure row — collect() returns it instead of hanging to
    its timeout."""
    from avenir_tpu.net import fault

    fleet, name, entry = _stranded_two_host_fleet(
        tmp_path, alive=[False, False])
    with fleet._lock:
        fleet._host_state = [fault.QUARANTINED, fault.QUARANTINED]
    fleet._sweep_leases(time.time())
    snap = fleet.fault_snapshot()
    assert snap["stats"]["abandoned"] == 1
    assert snap["stats"]["respools"] == 0
    assert snap["leases_outstanding"] == 0
    rows = {name: fleet._collected[name]}
    assert rows[name]["ok"] is False
    assert "stranded" in rows[name]["error"]


def test_stranded_request_waits_for_recovering_trail_host(tmp_path):
    """Trail exhausted but a trail host is RESTARTING: neither respool
    (nobody healthy yet) nor abandon (it may come back) — the sweep
    waits, then respools once the host serves again."""
    from avenir_tpu.net import fault

    fleet, name, entry = _stranded_two_host_fleet(
        tmp_path, alive=[False, False])
    with fleet._lock:
        fleet._host_state = [fault.RESTARTING, fault.RESTARTING]
    fleet._sweep_leases(time.time())
    snap = fleet.fault_snapshot()
    assert snap["stats"]["abandoned"] == 0
    assert snap["stats"]["respools"] == 0
    # host 0 comes back: the next sweep respools onto it

    class FakeProc:
        pid = 4242

        def poll(self):
            return None

    with fleet._lock:
        fleet._procs[0] = FakeProc()
        fleet._host_state[0] = fault.SERVING
    fleet._sweep_leases(time.time())
    assert fleet.fault_snapshot()["stats"]["respools"] == 1
    assert entry.lease.host == 0


def test_stranded_request_patience_bounds_the_wait(tmp_path):
    """Permanently wedged recovery: when the only hosts left stay
    RESTARTING/STALLED forever (a stall never respawns — only an exit
    code does), the stranded wait is bounded by stranded_patience_s,
    after which the request abandons in-band instead of riding the
    collect() timeout."""
    from avenir_tpu.net import fault

    fleet, name, entry = _stranded_two_host_fleet(
        tmp_path, alive=[False, False])
    with fleet._lock:
        fleet._host_state = [fault.STALLED, fault.RESTARTING]
    t0 = time.time()
    m0 = time.monotonic()
    fleet._sweep_leases(t0, mono=m0)     # starts the patience clock
    assert fleet.fault_snapshot()["stats"]["abandoned"] == 0
    assert entry.stranded_at is not None
    # patience is measured on the monotonic clock (a wall step must
    # never stretch or collapse it): advance mono past the bound
    fleet._sweep_leases(
        t0 + fleet.fault.stranded_patience_s + 1.0,
        mono=m0 + fleet.fault.stranded_patience_s + 1.0)
    snap = fleet.fault_snapshot()
    assert snap["stats"]["abandoned"] == 1
    assert snap["leases_outstanding"] == 0
    assert fleet._collected[name]["ok"] is False


def test_wall_clock_step_never_collapses_stranded_patience(tmp_path):
    """Two-clock discipline regression (graftlint --proto): stranded
    patience runs on the MONOTONIC clock, so an injected wall-clock
    step (NTP slam, +10000 s) must not abandon a stranded request
    early — only the monotonic clock crossing the bound may."""
    from avenir_tpu.net import fault

    fleet, name, entry = _stranded_two_host_fleet(
        tmp_path, alive=[False, False])
    with fleet._lock:
        fleet._host_state = [fault.STALLED, fault.RESTARTING]
    t0 = time.time()
    m0 = time.monotonic()
    fleet._sweep_leases(t0, mono=m0)     # starts the patience clock
    assert entry.stranded_at is not None
    # the step: wall leaps four hours, monotonic advances one second
    fleet._sweep_leases(t0 + 10000.0, mono=m0 + 1.0)
    assert fleet.fault_snapshot()["stats"]["abandoned"] == 0
    assert fleet.fault_snapshot()["leases_outstanding"] == 1
    # real elapsed time (monotonic) past the bound is what abandons
    fleet._sweep_leases(
        t0 + 10000.0,
        mono=m0 + fleet.fault.stranded_patience_s + 1.0)
    assert fleet.fault_snapshot()["stats"]["abandoned"] == 1


def test_wall_clock_step_never_fires_restart_backoff_early(
        tmp_path, monkeypatch):
    """Same discipline, the supervisor's restart backoff: a wall-clock
    step must neither fire the respawn early nor push it out — the
    backoff window is monotonic elapsed time."""

    class FakeProc:
        pid = 4242

        def __init__(self, rc=None):
            self.rc = rc

        def poll(self):
            return self.rc

    policy = FaultPolicy(poll_interval_s=0.05, max_restarts=3,
                         restart_backoff_base_s=5.0, hedge=False)
    fleet = Fleet(str(tmp_path / "fleet"), hosts=1, fault_policy=policy)
    spawned = []

    def fake_spawn(i):
        spawned.append(i)
        with fleet._lock:
            fleet._procs[i] = FakeProc()
            fleet._spawned_at[i] = time.time()
            fleet._spawned_mono[i] = time.monotonic()

    monkeypatch.setattr(fleet, "_spawn_host", fake_spawn)
    t0 = time.time()
    m0 = time.monotonic()
    with fleet._lock:
        fleet._procs[0] = FakeProc(rc=137)           # dead on arrival
        fleet._spawned_at = [t0]
        fleet._spawned_mono = [m0]
    fleet._supervise_hosts(t0, mono=m0)              # death -> backoff
    assert fleet.host_state(0) == "restarting" and spawned == []
    # wall leaps past any backoff; monotonic has barely moved: no fire
    fleet._supervise_hosts(t0 + 10000.0, mono=m0 + 1.0)
    assert spawned == []
    # monotonic elapses the 5 s backoff: the respawn fires now
    fleet._supervise_hosts(t0 + 10000.0, mono=m0 + 6.0)
    assert spawned == [0]
    assert fleet.fault_snapshot()["stats"]["restarts"] == 1


def test_probe_healthz_drives_listener_host_heartbeat(tmp_path):
    """fault.probe_healthz wired into the supervisor tick: a host
    registered with a listen address heartbeats through /healthz —
    a "serving" answer keeps it placeable, a quarantined overlay (or
    a dead listener) marks it stalled, recovery reinstates it. Driven
    through the real _supervise_hosts against a fake listener."""
    import http.server
    import threading

    from avenir_tpu.net import fault

    status = {"value": "serving"}

    class _Healthz(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            body = json.dumps({"status": status["value"]}).encode()
            code = 200 if status["value"] == "serving" else 503
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Healthz)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    addr = f"http://127.0.0.1:{httpd.server_address[1]}"

    class FakeProc:
        pid = 4242

        def poll(self):
            return None

    try:
        fleet = Fleet(str(tmp_path / "fleet"), hosts=1,
                      fault_policy=FaultPolicy(hedge=False,
                                               heartbeat_timeout_s=0.1),
                      listen_addresses={0: addr})
        with fleet._lock:
            fleet._procs[0] = FakeProc()
            # well past the boot grace: the probe is the heartbeat now
            fleet._spawned_at[0] = time.time() - 60.0
            fleet._spawned_mono[0] = time.monotonic() - 60.0
        # each check advances the monotonic clock past the probe memo
        # window (the supervisor re-probes at most every hb_timeout/2,
        # so wedged listeners cannot stall every tick)
        now = time.time()
        m0 = time.monotonic()
        step = fleet._hb_timeout
        fleet._supervise_hosts(now, mono=m0)
        assert fleet.host_state(0) == "serving"
        # the host's own listener reports quarantined (its overlay):
        # the front marks it stalled — no placements land on it
        status["value"] = "quarantined"
        fleet._supervise_hosts(now + step, mono=m0 + step)
        assert fleet.host_state(0) == "stalled"
        assert fleet.router.snapshot()["hosts"][0]["state"] == "stalled"
        # recovery: a serving probe reinstates placement
        status["value"] = "serving"
        fleet._supervise_hosts(now + 2 * step, mono=m0 + 2 * step)
        assert fleet.host_state(0) == "serving"
        # a dead listener (probe refused) is stalled too — the
        # exit-code check stays the authority on actual death
        httpd.shutdown()
        httpd.server_close()
        fleet._supervise_hosts(now + 3 * step, mono=m0 + 3 * step)
        assert fleet.host_state(0) == "stalled"
    finally:
        try:
            httpd.shutdown()
            httpd.server_close()
        except Exception:
            pass
        thread.join(10)


def test_requeued_refresh_cold_fallback(tmp_path):
    """Crash-resume composition: a refresh request landing on a host
    WITHOUT the corpus's checkpoint (what a lease requeue does after
    the warm host dies) falls back to the cold scan — never a wrong
    resume — and still writes byte-identical output."""
    csv = _seq(tmp_path, rows=400)
    fleet = Fleet(str(tmp_path / "fleet"), hosts=2, workers=1,
                  env=_SUB_ENV)
    fleet.start()
    try:
        # cold seed on the sticky host (host 0: first miss), writing
        # its managed checkpoint
        n1 = fleet.submit(_req_obj(csv, str(tmp_path / "rf1.txt"),
                                   mode="refresh"))
        r1 = fleet.collect([n1], timeout=240)[n1]
        assert r1["ok"]
        assert r1["counters"]["Resume:SkippedBytes"] == 0
        # warm repeat on the SAME host restores the carry
        n2 = fleet.submit(_req_obj(csv, str(tmp_path / "rf2.txt"),
                                   mode="refresh"))
        r2 = fleet.collect([n2], timeout=240)[n2]
        assert r2["ok"] and r2["counters"]["Resume:SkippedBytes"] > 0
        # the requeue shape: the same refresh forced onto the OTHER
        # host finds no local checkpoint -> cold scan, not a wrong
        # resume
        n3 = fleet.submit_to(1, _req_obj(csv, str(tmp_path / "rf3.txt"),
                                         mode="refresh"))
        r3 = fleet.collect([n3], timeout=240)[n3]
        assert r3["ok"] and r3["counters"]["Resume:SkippedBytes"] == 0
    finally:
        codes = fleet.stop()
    assert codes == [0, 0]
    twin = run_job("markovStateTransitionModel", MST_CONF, [csv],
                   str(tmp_path / "rf_ref.txt"))
    for out in ("rf1.txt", "rf2.txt", "rf3.txt"):
        with open(tmp_path / out, "rb") as fa, \
                open(twin.outputs[0], "rb") as fb:
            assert fa.read() == fb.read()


# ------------------------------------------------------------- stats merge
def test_stats_merges_snapshots_and_fleet_dirs(tmp_path):
    from avenir_tpu.obs.report import (expand_metrics_paths,
                                       merge_snapshots, render_metrics,
                                       stats_main)

    csv = _seq(tmp_path)
    paths = []
    for i in range(2):
        mp = str(tmp_path / f"host{i}" / "metrics.json")
        os.makedirs(os.path.dirname(mp), exist_ok=True)
        srv = JobServer(workers=1, metrics_path=mp,
                        state_root=str(tmp_path / f"state{i}"))
        t = srv.submit(JobRequest(
            "markovStateTransitionModel", MST_CONF, [csv],
            str(tmp_path / f"m{i}.txt"), tenant=f"t{i}"))
        with srv:
            t.result(240)
        paths.append(mp)
    snaps = [json.load(open(p)) for p in paths]
    merged = merge_snapshots(snaps)
    assert merged["hosts"] == 2
    assert merged["stats"]["served"] == 2.0
    # the histograms merged ADDITIVELY: merged count = sum of counts
    assert merged["hists"]["queue_wait_ms"]["count"] == sum(
        s["hists"]["queue_wait_ms"]["count"] for s in snaps)
    assert merged["hists"]["queue_wait_ms"]["max"] == max(
        s["hists"]["queue_wait_ms"]["max"] for s in snaps)
    text = render_metrics(merged)
    assert "2 hosts merged" in text
    # the CLI: N explicit paths, and the fleet-root glob, both exit 0
    assert stats_main(paths) == 0
    assert stats_main([str(tmp_path)]) == 0          # host*/ glob
    assert stats_main(paths + ["--json"]) == 0
    assert stats_main([str(tmp_path / "nope")]) == 2
    assert expand_metrics_paths([str(tmp_path)]) == paths


# ------------------------------------------------------------ load harness
def test_fleet_load_harness_inproc(tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import fleet_load
    finally:
        sys.path.pop(0)
    rc = fleet_load.main(["--requests", "4", "--tenants", "3",
                          "--corpora", "2", "--rows", "200",
                          "--rate", "50", "--arms", "inproc"])
    assert rc == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[0]["offered_jobs_per_min"] > 0
    arm = lines[1]
    assert arm["arm"] == "inproc"
    assert arm["served"] == 4 and arm["shed"] == 0
    assert arm["lost_requests"] == 0 and arm["retries"] == 0
    assert arm["jobs_per_min"] > 0
    assert arm["p99_queue_wait_ms"] >= arm["p50_queue_wait_ms"] >= 0.0
    # the shed-retry backoff: Retry-After analog doubled per attempt,
    # capped, ±20% jittered — the client half of the 429 contract
    rng = np.random.default_rng(0)
    first = [fleet_load._backoff_s(0, rng) for _ in range(16)]
    assert all(0.8 <= v <= 1.2 for v in first)
    assert min(first) < max(first)            # jittered, not lockstep
    assert all(6.4 <= fleet_load._backoff_s(9, rng) <= 9.6
               for _ in range(4))             # capped at 8s nominal


def test_fleet_load_harness_retries_sheds(monkeypatch):
    """A shed request is retried with backoff until served, never
    dropped: the fleet arm reports shed>0, retries>0 and
    lost_requests==0 — the soak contract."""
    import types

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import fleet_load
    finally:
        sys.path.pop(0)

    class FakeRouter:
        def affinity_hit_rate(self):
            return 1.0

    class FakeFleet:
        def __init__(self, root, hosts=2, workers=1, budget_mb=0.0):
            self.router = FakeRouter()
            self.n = 0
            self.sheds_left = 2

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

        def submit(self, obj, block=True, count_held=True):
            if self.sheds_left > 0:
                self.sheds_left -= 1
                return None
            self.n += 1
            return f"r{self.n}"

        def collect(self, names, timeout=0.0):
            return {n: {"ok": True} for n in names}

        def merged_metrics(self):
            return {"hists": {}}

    monkeypatch.setattr("avenir_tpu.net.fleet.Fleet", FakeFleet)
    args = types.SimpleNamespace(workers=1, budget_mb=1.0, seed=3,
                                 drain_timeout=30.0)
    load = [(0.0, {"i": i}) for i in range(3)]
    row = fleet_load.run_fleet(args, load, hosts=2)
    assert row["shed"] == 2 and row["retries"] >= 2
    assert row["served"] == 3 and row["lost_requests"] == 0
