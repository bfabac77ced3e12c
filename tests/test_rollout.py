"""Staged serving rollouts + per-tenant fairness (ISSUE 16).

Layers under test, bottom-up:

- tenancy units — DRR interleaving, token buckets, the brownout ladder,
  and the ladder-spec fallback, against bare :class:`TenantQueues` (no
  cluster, no clock slack);
- governor units — the verdict logic against a fake gateway: infra errors
  (dead replica, chaos kill) must NEVER roll back, NaN output / shadow
  divergence / model-attributable errors must, and a clean window
  promotes;
- faultinject grammar — the new ``bad_model`` / ``hot_tenant`` actions
  (string secondary keys ride the plan);
- end-to-end — real 2-node clusters:

  * ``bad_model`` on the canary cohort -> auto-rollback within one
    governor window, zero failed requests, rollback journaled (plus the
    tenant wire-compat assertions: tenant-tagged v2 frames and the
    id-less legacy client sharing one gateway);
  * ``kill_coordinator`` mid-canary -> the rollout rides out a
    control-plane failover (journal replay restores the in-flight state)
    and then promotes;
  * SIGKILL of the canary REPLICA mid-rollout -> no spurious rollback
    (infra exclusion), the restarted replica rejoins the canary cohort
    serving the CANDIDATE bundle, and promotion converges the fleet;
  * ``hot_tenant`` flood at 10x the rate limit -> only the hot tenant is
    shed (429-equivalent ``ServeThrottled``), other tenants' p99 stays
    within 2x their uncontended baseline.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from tensorflowonspark_tpu import cluster as tcluster
from tensorflowonspark_tpu import faultinject, serving, telemetry
from tensorflowonspark_tpu.checkpoint import bundle_signature, export_bundle
from tensorflowonspark_tpu.models import linear as linmod
from tensorflowonspark_tpu.serving import (
    GatewayClient,
    LegacyGatewayClient,
    RolloutGovernor,
    RolloutState,
    ServeThrottled,
    TenantQueues,
)
from tensorflowonspark_tpu.serving.rollout import divergence, nan_fraction
from tensorflowonspark_tpu.serving.tenancy import _parse_ladder

LINEAR = {"model": "linear", "in_dim": 4, "out_dim": 4}


# -- tenancy units -------------------------------------------------------------


class _Req:
    """Just enough request surface for TenantQueues (rows/offset/tenant)."""

    def __init__(self, tenant, nrows=1):
        self.tenant = tenant
        self.rows = list(range(nrows))
        self.offset = 0
        self.t_submit = time.monotonic()


def test_tenant_queues_drr_interleaves_backlogged_tenant():
    """A tenant with a deep backlog must not monopolize batch fill: the
    light tenant's rows land within the first DRR rotation turns."""
    q = TenantQueues(queue_limit=64, rate=0.0)
    q.append(_Req("bulk", 100))
    q.append(_Req("light", 8))
    order = []
    for _ in range(6):
        req = q.next_for_batch()
        take = min(4, len(req.rows) - req.offset)
        req.offset += take
        order.append(req.tenant)
        q.charge(req, take)
    assert "light" in order[:4], order
    assert set(q.depths()) <= {"bulk", "light"}


def test_tenant_queues_weighted_drr_grants_proportional_deficit():
    """A weight-3 tenant drains ~3x the rows of a weight-1 tenant per
    rotation cycle (quantum x weight deficit grants)."""
    q = TenantQueues(queue_limit=256, rate=0.0,
                     weights={"gold": 3.0, "bronze": 1.0})
    q.append(_Req("gold", 120))
    q.append(_Req("bronze", 120))
    pulled = {"gold": 0, "bronze": 0}
    for _ in range(16):
        req = q.next_for_batch()
        take = min(4, len(req.rows) - req.offset)
        req.offset += take
        pulled[req.tenant] += take
        q.charge(req, take)
    assert pulled["gold"] >= 2 * pulled["bronze"], pulled


def test_tenant_queues_token_bucket_throttles_and_refills():
    q = TenantQueues(queue_limit=64, rate=20.0)
    assert q.admission_error("t", 20) is None  # the full burst fits
    err = q.admission_error("t", 1)
    assert isinstance(err, ServeThrottled)
    assert "rate" in str(err)
    time.sleep(0.3)  # ~6 tokens refill at 20 rows/s
    assert q.admission_error("t", 2) is None


def test_tenant_queues_brownout_sheds_only_over_share_tenant():
    """Level-2 brownout: the tenant past its weight-proportional queue
    share is shed; a tenant under its share is still admitted."""
    q = TenantQueues(queue_limit=10, rate=0.0, ladder="0.5,0.8")
    for _ in range(7):
        q.append(_Req("pig"))
    q.append(_Req("mouse"))
    assert q.shed_level() == 2
    err = q.admission_error("pig", 1)
    assert isinstance(err, ServeThrottled) and "brownout" in str(err)
    assert q.admission_error("mouse", 1) is None
    # remove() keeps the count honest (expiry path)
    victim = next(iter(q))
    q.remove(victim)
    assert len(q) == 7


def test_parse_ladder_falls_back_on_bad_spec():
    assert _parse_ladder("0.3,0.9") == (0.3, 0.9)
    assert _parse_ladder("junk") == (0.5, 0.8)
    assert _parse_ladder("") == (0.5, 0.8)
    assert _parse_ladder("2.0") == (0.5, 0.8)  # fractions, not multiples


# -- faultinject grammar -------------------------------------------------------


def test_fault_plan_parses_bad_model_and_hot_tenant():
    plan = faultinject.FaultPlan.parse(
        "bad_model:nan=1,ms=50;hot_tenant:mult=10,tenant=burst")
    armed = {a.name: a for a in plan._actions}
    assert armed["bad_model"].threshold == 1
    assert armed["bad_model"].extra["ms"] == 50.0
    assert armed["hot_tenant"].threshold == 10
    assert armed["hot_tenant"].extra["tenant"] == "burst"


# -- governor units (fake gateway) ---------------------------------------------


class _FakeGateway:
    def __init__(self):
        self.promoted: list = []
        self.rolled_back: list = []
        self.journal: list = []

    def _promote_rollout(self, gov):
        self.promoted.append(gov.state.candidate)

    def _rollback_rollout(self, gov, reason):
        self.rolled_back.append(reason)

    def _note_rollout(self, payload):
        self.journal.append(payload)


def _governor(**kw):
    gw = _FakeGateway()
    state = RolloutState(candidate="/cand", prior="/prior", canary=[1],
                         pct=50, shadow=True)
    kw.setdefault("window_secs", 0.4)
    kw.setdefault("min_canary_samples", 1)
    kw.setdefault("poll_secs", 0.05)
    return gw, RolloutGovernor(gw, state, **kw)


def test_governor_promotes_clean_window_and_ignores_infra_errors():
    """Transport failures (the chaos-kill class) are recovery's problem:
    a canary throwing ConnectionError/FaultInjected must still promote."""
    gw, gov = _governor()
    for _ in range(4):
        gov.observe("primary", 0, True, 0.01, [np.ones(2)], None, None)
        gov.observe("canary", 1, True, 0.01, [np.ones(2)], None, None)
    gov.observe("canary", 1, False, 0.0, None, ConnectionError("dead"), None)
    gov.observe("canary", 1, False, 0.0, None,
                faultinject.FaultInjected("sever"), None)
    gov.start()
    assert gov.wait(10.0) == "promoted"
    assert gw.promoted == ["/cand"] and not gw.rolled_back
    assert gw.journal[-1]["status"] == "promoted"
    assert gov.status()["infra_errors"] == 2


def test_governor_rolls_back_on_nan_outputs():
    gw, gov = _governor()
    gov.observe("primary", 0, True, 0.01, [np.ones(2)], None, None)
    gov.observe("canary", 1, True, 0.01, [np.array([np.nan, 1.0])], None,
                None)
    gov.start()
    assert gov.wait(10.0) == "rolled_back"
    assert gw.rolled_back and "NaN" in gw.rolled_back[0]
    assert gov.state.rollback_secs() is not None
    assert gw.journal[-1]["status"] == "rolled_back"


def test_governor_rolls_back_on_shadow_divergence():
    gw, gov = _governor()
    primary_out = [np.array([1.0, 2.0])]
    gov.observe("canary", 1, True, 0.01, [np.array([1.0, 3.5])], None,
                primary_out)  # mirror: canary answer vs primary's
    gov.start()
    assert gov.wait(10.0) == "rolled_back"
    assert "diverges" in gw.rolled_back[0]


def test_governor_rolls_back_on_model_errors_absent_on_primary():
    gw, gov = _governor()
    gov.observe("primary", 0, True, 0.01, [np.ones(2)], None, None)
    gov.observe("canary", 1, False, 0.01, None,
                RuntimeError("bad output head"), None)
    gov.start()
    assert gov.wait(10.0) == "rolled_back"
    assert "model-attributable" in gw.rolled_back[0]


def test_governor_manual_promote_and_stop_abort():
    gw, gov = _governor(auto_promote=False, window_secs=0.1)
    gov.observe("canary", 1, True, 0.01, [np.ones(2)], None, None)
    gov.start()
    time.sleep(0.3)
    assert gov.active()  # auto_promote off: a clean window does NOT resolve
    assert gov.promote() == "promoted"
    assert gw.promoted == ["/cand"]

    gw2, gov2 = _governor()
    gov2.stop()  # never started/resolved -> aborted + journaled
    assert gov2.state.status == "aborted"
    assert gw2.journal[-1]["status"] == "aborted"


def test_divergence_and_nan_helpers():
    assert divergence([np.ones(2)], [np.ones(2)]) == 0.0
    assert divergence([{"y": np.ones(2)}], [{"z": np.ones(2)}]) == 1.0
    assert divergence([np.ones(3)], [np.ones(2)]) == 1.0  # shape mismatch
    assert divergence([np.array([np.nan])], [np.ones(1)]) == 1.0
    assert divergence([3], [3]) == 0.0 and divergence([3], [4]) > 0
    assert nan_fraction([np.array([np.nan, 1.0])]) == 0.5
    assert nan_fraction([np.ones(4)]) == 0.0


# -- end-to-end ----------------------------------------------------------------


@pytest.fixture
def arm_driver_faults(monkeypatch):
    """Arm TOS_FAULTINJECT in the DRIVER process (kill_coordinator and
    hot_tenant live there) and guarantee disarm afterwards."""
    def arm(spec: str) -> None:
        monkeypatch.setenv("TOS_FAULTINJECT", spec)
        faultinject.init_from_env(force=True)

    yield arm
    monkeypatch.delenv("TOS_FAULTINJECT", raising=False)
    faultinject.init_from_env(force=True)


def _serve_cluster(tmp_path, *, scale=2.0, elastic=False, per_node_env=None,
                   env=None, max_batch=4, log_dir=""):
    export = str(tmp_path / "bundle")
    export_bundle(export, linmod.init_params(LINEAR, scale=scale), LINEAR)
    cluster = tcluster.run(
        serving.serving_loop,
        {"export_dir": export, "max_batch": max_batch},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        heartbeat_interval=0.5,
        per_node_env=per_node_env,
        env=env,
        log_dir=log_dir,
        reservation_timeout=120.0,
        elastic=elastic,
    )
    return cluster, export


def _candidate(tmp_path, scale):
    cand = str(tmp_path / "candidate")
    export_bundle(cand, linmod.init_params(LINEAR, scale=scale), LINEAR)
    return cand


@pytest.mark.chaos
def test_bad_model_canary_auto_rolls_back_with_zero_failed_requests(
        tmp_path):
    """The headline acceptance: stage a candidate that the ``bad_model``
    chaos hook corrupts (NaN outputs on CANDIDATE bundles only); the
    governor must detect it and roll the canaries back within one window,
    with every driven request answered (primary answers always correct)
    and the rollback journaled.  The same boot pins the tenant wire
    compatibility: tenant-tagged pipelined frames and the id-less legacy
    client share the gateway."""
    telemetry.reset()
    chaos = {"TOS_FAULTINJECT": "bad_model:nan=1"}
    cluster, export = _serve_cluster(
        tmp_path, scale=2.0, per_node_env=[dict(chaos), dict(chaos)])
    try:
        gw = cluster.serve(export, max_batch=4, max_delay_ms=2.0,
                           reload_poll_secs=0)
        base = np.arange(4, dtype=np.float32)

        # -- wire-compat satellite (before any rollout exists) --
        host, port = gw.endpoint
        np.testing.assert_allclose(
            gw.predict([base], timeout=60.0, tenant="driver-side")[0],
            base * 2.0)
        modern = GatewayClient("127.0.0.1", port, cluster.authkey,
                               tenant="team-a")
        legacy = LegacyGatewayClient("127.0.0.1", port, cluster.authkey)
        try:
            np.testing.assert_allclose(
                modern.predict([base + 1], timeout=60.0)[0], (base + 1) * 2.0)
            np.testing.assert_allclose(  # per-call override rides the frame
                modern.predict([base + 2], timeout=60.0, tenant="team-b")[0],
                (base + 2) * 2.0)
            # the id-less 3-tuple wire shape still answers (anonymous tenant)
            np.testing.assert_allclose(
                legacy.predict([base + 3], timeout=60.0)[0], (base + 3) * 2.0)
            assert legacy.ping()
        finally:
            modern.close()
            legacy.close()

        # -- the rollout: candidate identical in weights, corrupted by chaos
        cand = _candidate(tmp_path, scale=2.0)
        gov = gw.rollout(cand, canary_pct=50, shadow=True, window_secs=3.0)
        assert gw._router.cohort_members("canary") == [0]
        errors: list = []
        driven = 0
        deadline = time.monotonic() + 60.0
        while gov.active() and time.monotonic() < deadline:
            try:
                gw.predict([base + driven], timeout=30.0)
            except Exception as e:  # noqa: BLE001 - asserted empty below
                errors.append(repr(e))
            driven += 1
        assert gov.wait(30.0) == "rolled_back", gov.status()
        # zero failed requests: canary answers may be NaN pre-rollback (that
        # is what canarying risks), but nothing ever errored or misrouted
        assert not errors, errors[:3]
        assert "NaN" in (gov.state.reason or "") or \
            "diverges" in (gov.state.reason or ""), gov.state.reason
        # rollback within one governor window of detection
        assert gov.status()["rollback_secs"] is not None
        assert gov.status()["rollback_secs"] < 30.0
        assert telemetry.counter("serve.rollbacks_total").value() == 1
        assert telemetry.counter("serve.shadow_mirrors").value() >= 1
        # the split is gone and the PRIOR bundle serves everywhere
        assert gw._router.cohort_members("canary") == []
        for i in range(6):
            np.testing.assert_allclose(
                gw.predict([base + i], timeout=60.0)[0], (base + i) * 2.0)
        # journaled: the coordinator's rollout registry has the abort story
        reg = cluster.coordinator.rollout_state()
        assert any(v.get("status") == "rolled_back"
                   and v.get("candidate") == cand for v in reg.values()), reg
        # a fresh rollout is allowed after resolution (state machine back
        # to idle) — and refusing fleet reloads mid-rollout was enforced
        assert gw.rollout_status()["status"] == "rolled_back"
    finally:
        cluster.shutdown(timeout=120.0)


@pytest.mark.chaos
def test_rollout_survives_coordinator_kill_then_promotes(
        tmp_path, monkeypatch, arm_driver_faults):
    """``kill_coordinator`` mid-canary: the data plane keeps serving, the
    rollout keeps governing, and the journal replay restores the in-flight
    rollout state across the failover — after which promotion converges
    the fleet onto the candidate."""
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "0.2")
    telemetry.reset()
    cluster, export = _serve_cluster(tmp_path, scale=2.0,
                                     env={"TOS_FAULTINJECT": ""},
                                     log_dir=str(tmp_path / "logs"))
    try:
        gw = cluster.serve(export, max_batch=4, max_delay_ms=2.0,
                           listen=False, reload_poll_secs=0)
        base = np.arange(4, dtype=np.float32)
        cand = _candidate(tmp_path, scale=3.0)
        gov = gw.rollout(cand, canary_pct=50, shadow=False,
                         auto_promote=False, window_secs=2.0,
                         latency_factor=50.0, latency_floor_secs=5.0)
        # arm AFTER the rollout is in flight so the crash cannot land
        # inside the canary ctl round — the scenario is a failover UNDER
        # an established rollout (heartbeats advance the op clock)
        arm_driver_faults("kill_coordinator:after_ops=10")
        driven = 0
        deadline = time.monotonic() + 90.0
        while cluster.coordinator.epoch < 1 and time.monotonic() < deadline:
            out = gw.predict([base + driven], timeout=30.0)[0]
            # canary-routed answers are x3 (the candidate), primary x2 —
            # never junk, never an error
            ok2 = np.allclose(out, (base + driven) * 2.0)
            ok3 = np.allclose(out, (base + driven) * 3.0)
            assert ok2 or ok3, out
            driven += 1
            time.sleep(0.01)
        assert cluster.coordinator.epoch >= 1, \
            "the coordinator kill never fired mid-canary"
        # still mid-canary: the failover neither resolved nor aborted it
        assert gov.active()
        assert telemetry.counter("serve.rollbacks_total").value() == 0
        # journal replay restored the IN-FLIGHT rollout state
        reg = cluster.coordinator.rollout_state()
        assert any(v.get("status") == "canary" and v.get("candidate") == cand
                   and v.get("canary") == [0] for v in reg.values()), reg
        # operator promotes; the fleet converges on the candidate
        assert gov.promote() == "promoted"
        deadline = time.monotonic() + 60.0
        streak = 0
        while streak < 6 and time.monotonic() < deadline:
            out = gw.predict([base], timeout=30.0)[0]
            streak = streak + 1 if np.allclose(out, base * 3.0) else 0
        assert streak >= 6, "fleet never converged on the promoted candidate"
        reg = cluster.coordinator.rollout_state()
        assert any(v.get("status") == "promoted" for v in reg.values()), reg
        assert gw.export_dir == cand  # the watcher now tracks the candidate
    finally:
        cluster.shutdown(timeout=120.0)
    assert cluster.coordinator.errors() == []


@pytest.mark.chaos
def test_canary_replica_sigkill_no_spurious_rollback_and_cohort_rejoin(
        tmp_path, monkeypatch):
    """SIGKILL the canary REPLICA mid-rollout: the in-flight canary batch
    retries on the primary cohort (every request still answered), the
    governor must NOT read the transport failure as a model regression,
    and the supervised restart must rejoin the replica into the CANARY
    cohort serving the CANDIDATE bundle (recovery replays the cohort's
    reload ctl)."""
    monkeypatch.setenv("TOS_DEAD_NODE_TIMEOUT", "4")
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "0.2")
    telemetry.reset()
    # the canary cohort is EXECUTOR 0 (asserted below), and executor ids are
    # handed out in registration order, not launch order: aimed through
    # per_node_env[0] the kill hit the lone PRIMARY replica whenever the
    # second process registered first, and a batch whose retry has no other
    # primary to go to fails ("no healthy serving replica available")
    cluster, export = _serve_cluster(
        tmp_path, scale=2.0, elastic=True,
        env={"TOS_FAULTINJECT": "kill:after_batches=3,incarnation=0,executor=0"})
    try:
        gw = cluster.serve(export, max_batch=4, max_delay_ms=2.0,
                           listen=False, reload_poll_secs=0)
        base = np.arange(4, dtype=np.float32)
        cand = _candidate(tmp_path, scale=3.0)
        gov = gw.rollout(cand, canary_pct=50, shadow=False,
                         auto_promote=False, window_secs=2.0,
                         latency_factor=50.0, latency_floor_secs=5.0)
        assert gw._router.cohort_members("canary") == [0]
        errors: list = []
        driven = 0
        deadline = time.monotonic() + 60.0
        while (telemetry.counter("serve.replica_failures").value() == 0
               and time.monotonic() < deadline):
            try:
                out = gw.predict([base + driven], timeout=90.0)[0]
                assert (np.allclose(out, (base + driven) * 2.0)
                        or np.allclose(out, (base + driven) * 3.0)), out
            except Exception as e:  # noqa: BLE001 - asserted empty below
                errors.append(repr(e))
            driven += 1
        assert not errors, errors[:3]
        assert telemetry.counter("serve.replica_failures").value() >= 1, \
            "the canary kill never fired"
        # requests keep flowing with the canary DOWN: cohort fallback +
        # demotion-retry keep every answer on the healthy primary (x3 only
        # if the supervised restart already rejoined with the candidate)
        for i in range(8):
            out = gw.predict([base + i], timeout=90.0)[0]
            assert (np.allclose(out, (base + i) * 2.0)
                    or np.allclose(out, (base + i) * 3.0)), out
        # the governor saw only infra errors: NO rollback
        assert gov.active(), gov.status()
        assert telemetry.counter("serve.rollbacks_total").value() == 0
        # the supervised restart rejoins replica 0 into the CANARY cohort
        # (recovery replays the candidate reload before re-admission)
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline and (
                gw.healthy_replicas() != [0, 1]
                or gw._router.cohort_members("canary") != [0]):
            time.sleep(0.5)
        assert gw.healthy_replicas() == [0, 1]
        assert gw._router.cohort_members("canary") == [0]
        # the rejoined canary serves the CANDIDATE: drive until a x3 answer
        # proves the replayed ctl loaded it (canary takes every 2nd batch)
        deadline = time.monotonic() + 60.0
        seen_candidate = False
        while not seen_candidate and time.monotonic() < deadline:
            out = gw.predict([base], timeout=60.0)[0]
            seen_candidate = np.allclose(out, base * 3.0)
        assert seen_candidate, \
            "restarted canary never served the candidate bundle"
        assert gov.promote() == "promoted"
        assert gw._router.cohort_members("canary") == []
    finally:
        cluster.shutdown(timeout=120.0)
    assert telemetry.counter("elastic.restarts_total").value() >= 1


@pytest.mark.chaos
def test_hot_tenant_flood_sheds_only_the_hot_tenant(tmp_path, monkeypatch,
                                                    arm_driver_faults):
    """``hot_tenant`` drives one tenant to 10x its rate limit: ONLY that
    tenant sees shed (``ServeThrottled``) responses, every other tenant's
    request stream stays error-free with p99 within 2x its uncontended
    baseline."""
    monkeypatch.setenv("TOS_SERVE_TENANT_RATE", "400")
    telemetry.reset()
    # the chaos hook multiplies the HOT tenant's bucket charge by 10
    arm_driver_faults("hot_tenant:mult=10,tenant=hot")
    cluster, export = _serve_cluster(tmp_path, scale=2.0,
                                     env={"TOS_FAULTINJECT": ""})
    try:
        gw = cluster.serve(export, max_batch=8, max_delay_ms=2.0,
                           listen=False, reload_poll_secs=0)
        base = np.arange(4, dtype=np.float32)

        def drive(tenant, secs, out_lat, out_err, rows=1, pace=0.02):
            deadline = time.monotonic() + secs
            i = 0
            while time.monotonic() < deadline:
                t0 = time.monotonic()
                try:
                    got = gw.predict([base + i] * rows, timeout=30.0,
                                     tenant=tenant)
                    np.testing.assert_allclose(got[0], (base + i) * 2.0)
                    out_lat.append(time.monotonic() - t0)
                except ServeThrottled:
                    out_err.append("throttled")
                i += 1
                if pace:
                    time.sleep(pace)

        # phase 1: uncontended baseline for the well-behaved tenants
        base_lat: dict = {"a": [], "b": []}
        base_err: dict = {"a": [], "b": []}
        threads = [threading.Thread(target=drive,
                                    args=(t, 2.5, base_lat[t], base_err[t]))
                   for t in ("a", "b")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not base_err["a"] and not base_err["b"]

        # phase 2: the hot tenant floods (16-row requests, no pacing =
        # 10x its effective 40 rows/s budget) while a and b keep their
        # modest pace
        lat: dict = {"a": [], "b": [], "hot": []}
        errs: dict = {"a": [], "b": [], "hot": []}
        threads = [threading.Thread(target=drive,
                                    args=(t, 4.0, lat[t], errs[t]))
                   for t in ("a", "b")]
        threads.append(threading.Thread(
            target=drive, args=("hot", 4.0, lat["hot"], errs["hot"]),
            kwargs={"rows": 16, "pace": 0.0}))
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        # only the hot tenant was shed — and it WAS shed (the flood did
        # not ride the queue at everyone else's expense)
        assert errs["hot"], "hot tenant was never throttled at 10x its rate"
        assert not errs["a"] and not errs["b"], (errs["a"][:2], errs["b"][:2])
        assert telemetry.counter("serve.throttled_total").value() >= 1
        assert lat["a"] and lat["b"]
        for t in ("a", "b"):
            p99_base = float(np.percentile(base_lat[t], 99))
            p99_hot = float(np.percentile(lat[t], 99))
            # within 2x uncontended (+ a small absolute floor so a single
            # scheduler hiccup on the 1-core CI box cannot flake the run)
            assert p99_hot <= max(2.0 * p99_base, p99_base + 0.25), (
                t, p99_base, p99_hot)
    finally:
        cluster.shutdown(timeout=120.0)


def test_bundle_signature_tracks_reexport(tmp_path):
    export = str(tmp_path / "sig")
    export_bundle(export, linmod.init_params(LINEAR, scale=2.0), LINEAR)
    sig1 = bundle_signature(export)
    assert sig1 and all(len(entry) == 3 for entry in sig1)
    assert bundle_signature(export) == sig1  # stable while untouched
    time.sleep(0.01)
    export_bundle(export, linmod.init_params(LINEAR, scale=3.0), LINEAR)
    assert bundle_signature(export) != sig1
    assert bundle_signature(str(tmp_path / "missing")) == ()
