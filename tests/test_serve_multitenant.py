"""Multi-tenant serving (hyperspace_tpu.serve): per-tenant quotas,
weighted-fair scheduling, circuit breaking, load shedding, cancel, the
client retry helper, and the mixed-tenant soak scenario.

Determinism disciplines: fairness tests use PAUSED servers with ONE
worker so the dispatch order is the scheduler recurrence, not a thread
race; breaker tests drive state with deadline misses (queue-time misses
are exact on a paused server) and sub-100ms cooldowns; the soak test is
the one place real concurrency runs, and it asserts INVARIANTS
(resolution, wholesale snapshots, share bounds, counter conservation),
never timings.
"""

import threading
import time

import numpy as np
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.exec.hbm_cache import hbm_cache
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.index.index_config import IndexConfig
from hyperspace_tpu.plan.expr import col, lit
from hyperspace_tpu.serve import (
    AdmissionRejected,
    QueryCancelled,
    QueryServer,
    ServeConfig,
    submit_with_retry,
)
from hyperspace_tpu.serve.tenancy import (
    CLOSED,
    OPEN,
    CircuitBreaker,
    TenantPolicy,
    TenantState,
)
from hyperspace_tpu.session import HyperspaceSession
from hyperspace_tpu.storage import parquet_io
from hyperspace_tpu.storage.columnar import ColumnarBatch
from hyperspace_tpu.telemetry.metrics import metrics


@pytest.fixture(autouse=True)
def _force_residency(monkeypatch):
    monkeypatch.setenv("HYPERSPACE_TPU_HBM", "force")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_MIN_ROWS", "1")
    hbm_cache.reset()
    yield
    hbm_cache.reset()


N_ROWS = 40_000


def _source(n=N_ROWS, seed=0):
    rng = np.random.default_rng(seed)
    return ColumnarBatch.from_pydict(
        {
            "k": rng.integers(0, 10_000, n).astype(np.int64),
            "v": rng.integers(0, 1000, n).astype(np.int64),
        }
    )


@pytest.fixture
def env(tmp_path):
    batch = _source()
    src = tmp_path / "data"
    src.mkdir()
    parquet_io.write_parquet(src / "part-0.parquet", batch)
    conf = HyperspaceConf(
        {C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"), C.INDEX_NUM_BUCKETS: 4}
    )
    session = HyperspaceSession(conf)
    hs = Hyperspace(session)
    df = session.read.parquet(str(src))
    hs.create_index(df, IndexConfig("midx", ["k"], ["v"]))
    session.enable_hyperspace()
    assert hs.prefetch_index("midx")
    return session, hs, src, batch


def _lookup(session, src, key):
    return (
        session.read.parquet(str(src))
        .filter(col("k") == lit(int(key)))
        .select("k", "v")
    )


def _rows(b):
    return sorted(zip(b.columns["k"].data.tolist(), b.columns["v"].data.tolist()))


# ---------------------------------------------------------------------------
# weighted-fair scheduling
# ---------------------------------------------------------------------------
def test_weighted_fair_dispatch_shares(env):
    """Weights 1/2/4 with every tenant backlogged: dispatch-turn shares
    over any full cycle match the weights exactly (smooth WRR), and in
    particular sit within the 2x fairness bound the soak scores."""
    session, hs, src, batch = env
    for name, w in (("bronze", 1), ("silver", 2), ("gold", 4)):
        session.conf.set(f"{C.SERVE_TENANT_PREFIX}.{name}.weight", w)
    server = QueryServer(
        session,
        ServeConfig(max_workers=1, max_queue=256, batch_max=1, autostart=False),
    )
    keys = [int(batch.columns["k"].data[i]) for i in range(24)]
    tickets = []
    for i, k in enumerate(keys):
        for name in ("bronze", "silver", "gold"):
            tickets.append(
                server.submit(_lookup(session, src, k), tenant=name)
            )
    server.start()
    for t in tickets:
        t.result(timeout=300)
    order = list(server._dispatch_order)
    assert len(order) == len(tickets)
    # first two full cycles (weights sum to 7): exact weighted shares
    prefix = order[:14]
    share = {n: prefix.count(n) for n in ("bronze", "silver", "gold")}
    assert share == {"bronze": 2, "silver": 4, "gold": 8}
    # the acceptance bound: while every tenant is backlogged (gold's 24
    # queries last 42 turns at 4/7 share), each tenant's dispatch share
    # sits within 2x of its weight share; after a queue empties the
    # remaining tenants legitimately absorb its turns
    window = order[:42]
    total_w = 7
    for name, w in (("bronze", 1), ("silver", 2), ("gold", 4)):
        got = window.count(name) / len(window)
        want = w / total_w
        assert want / 2 <= got <= want * 2, (name, got, want)
    stats = server.stats()
    assert stats["overload"]["dispatch_share"]["gold"] == order.count("gold")
    assert stats["tenants"]["gold"]["weight"] == 4.0
    server.close()


def test_tenant_queue_cap_isolates_bursting_tenant(env):
    """One tenant's burst hits ITS queue cap; the other tenant keeps
    admitting — the global queue never fills with one tenant's work."""
    session, hs, src, batch = env
    session.conf.set(f"{C.SERVE_TENANT_PREFIX}.bursty.maxQueue", 3)
    server = QueryServer(
        session, ServeConfig(max_workers=1, max_queue=64, autostart=False)
    )
    for i in range(3):
        server.submit(_lookup(session, src, i), tenant="bursty")
    with pytest.raises(AdmissionRejected) as exc:
        server.submit(_lookup(session, src, 9), tenant="bursty")
    assert exc.value.reason == "tenant_queue_full"
    assert exc.value.tenant == "bursty"
    assert exc.value.tenant_depth == 3
    assert exc.value.retry_after_s > 0
    # the quiet tenant is untouched by the burst
    t_ok = server.submit(_lookup(session, src, 1), tenant="quiet")
    server.start()
    assert t_ok.result(timeout=120) is not None
    stats = server.stats()
    assert stats["tenants"]["bursty"]["shed"] == 1
    assert stats["tenants"]["quiet"]["shed"] == 0
    server.close()


def test_inflight_cap_holds_tenant_queries_back(env):
    """maxInflight=1: a tenant's second query stays QUEUED while its
    first executes even with idle workers; other tenants use them."""
    session, hs, src, batch = env
    session.conf.set(f"{C.SERVE_TENANT_PREFIX}.capped.maxInflight", 1)
    gate = threading.Event()
    released = threading.Event()
    orig = QueryServer._run_plan

    def gated(self, req):
        if req.ticket.tenant == "capped" and not released.is_set():
            released.set()
            gate.wait(30)
        return orig(self, req)

    QueryServer._run_plan = gated
    try:
        # batch_max=1: same-table lookups must NOT coalesce here — a
        # cross-tenant batch would serve t2/t3 on one dispatch and the
        # in-flight observation below would race the widening
        server = QueryServer(
            session, ServeConfig(max_workers=2, batch_max=1, autostart=False)
        )
        key = int(batch.columns["k"].data[0])
        t1 = server.submit(_lookup(session, src, key), tenant="capped")
        t2 = server.submit(_lookup(session, src, key), tenant="capped")
        t3 = server.submit(_lookup(session, src, key), tenant="other")
        server.start()
        assert released.wait(30)  # first capped query is executing
        # the other tenant's query flows through the second worker
        assert t3.result(timeout=120) is not None
        # the capped tenant's second query is still held at its cap
        assert not t2.done()
        snap = server.stats()["tenants"]["capped"]
        assert snap["inflight"] == 1 and snap["queue_depth"] == 1
        gate.set()
        assert t1.result(timeout=120) is not None
        assert t2.result(timeout=120) is not None
        server.close()
    finally:
        gate.set()
        QueryServer._run_plan = orig


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------
def test_breaker_opens_on_misses_and_recovers_via_half_open_probe(env):
    session, hs, src, batch = env
    session.conf.set(C.SERVE_BREAKER_MISS_THRESHOLD, 2)
    # cooldown with headroom: a loaded-runner stall between the misses
    # and the rejection assert below must not lapse it (the repo's
    # standing deflake discipline for sub-100ms timing windows)
    session.conf.set(C.SERVE_BREAKER_OPEN_SECONDS, 0.5)
    server = QueryServer(session, ServeConfig(max_workers=1, autostart=False))
    key = int(batch.columns["k"].data[0])
    # two queued queries whose deadlines lapse before any worker exists
    doomed = [
        server.submit(_lookup(session, src, key), deadline_s=0.001, tenant="t")
        for _ in range(2)
    ]
    time.sleep(0.02)
    server.start()
    for t in doomed:
        with pytest.raises(Exception):
            t.result(timeout=60)
    # consecutive misses crossed the threshold: the circuit is OPEN and
    # rejects immediately with the remaining cooldown as retry-after
    with pytest.raises(AdmissionRejected) as exc:
        server.submit(_lookup(session, src, key), tenant="t")
    assert exc.value.reason == "breaker_open"
    assert 0 < exc.value.retry_after_s <= 0.5 + 0.01
    snap = server.stats()["tenants"]["t"]
    assert snap["breaker"]["state"] == OPEN
    assert snap["breaker"]["opens"] == 1
    assert snap["rejected_breaker"] == 1
    # cooldown lapses -> HALF-OPEN: the next submission is the probe,
    # and its clean finish closes the circuit
    time.sleep(0.55)
    probe = server.submit(_lookup(session, src, key), tenant="t")
    assert probe.result(timeout=120) is not None
    snap = server.stats()["tenants"]["t"]
    assert snap["breaker"]["state"] == CLOSED
    assert snap["breaker"]["probes"] >= 1
    assert snap["breaker"]["closes"] == 1
    assert metrics.counter("serve.breaker.opened") >= 1
    assert metrics.counter("serve.breaker.closed") >= 1
    # healthy again: normal submissions admit
    assert server.submit(
        _lookup(session, src, key), tenant="t"
    ).result(timeout=120) is not None
    server.close()


def test_breaker_probe_cancel_frees_the_half_open_slot(env):
    """Regression (review round): cancelling the half-open PROBE while
    it is queued must free the probe slot — leaking it wedged the
    breaker half-open with every later submission rejected forever."""
    session, hs, src, batch = env
    session.conf.set(C.SERVE_BREAKER_MISS_THRESHOLD, 2)
    session.conf.set(C.SERVE_BREAKER_OPEN_SECONDS, 0.05)
    server = QueryServer(session, ServeConfig(max_workers=1, autostart=False))
    key = int(batch.columns["k"].data[0])
    for _ in range(2):
        server.submit(_lookup(session, src, key), deadline_s=0.001, tenant="t")
    time.sleep(0.02)
    server.start()
    time.sleep(0.1)  # misses recorded, cooldown lapsed
    # the next submission is the probe — cancel it before dispatch;
    # pause dispatch by filling the worker with a slow... simpler: the
    # race is cancel-before-dispatch, so win it deterministically by
    # submitting while no backlog exists and cancelling immediately —
    # if dispatch wins, cancel() returns False and the probe decides
    # normally; either way the breaker must NOT wedge
    probe = server.submit(_lookup(session, src, key), tenant="t")
    assert probe._is_probe
    cancelled = probe.cancel()
    if cancelled:
        with pytest.raises(QueryCancelled):
            probe.result(timeout=5)
    else:
        probe.result(timeout=120)
    # the tenant recovers: within a couple of probe windows a
    # submission is admitted and closes the circuit
    deadline = time.monotonic() + 10
    while True:
        try:
            t = server.submit(_lookup(session, src, key), tenant="t")
            t.result(timeout=120)
            break
        except AdmissionRejected:
            assert time.monotonic() < deadline, "breaker wedged half-open"
            time.sleep(0.03)
    assert server.stats()["tenants"]["t"]["breaker"]["state"] == CLOSED
    server.close()


def test_breaker_probe_miss_reopens():
    """Unit-level: a HALF-OPEN probe that misses re-opens immediately;
    only one probe is admitted per half-open window."""
    b = CircuitBreaker(miss_threshold=2, open_s=10.0)
    b.record_miss_locked(now=0.0)
    b.record_miss_locked(now=0.0)
    assert b.state == OPEN and b.open_until == 10.0
    # still cooling: rejected with the remaining cooldown
    ok, retry = b.admit_locked(now=5.0)
    assert not ok and retry == pytest.approx(5.0)
    # cooldown over: exactly ONE probe admitted
    ok, _ = b.admit_locked(now=11.0)
    assert ok and b.state == "half_open" and b.probe_inflight
    ok2, _ = b.admit_locked(now=11.0)
    assert not ok2
    # a leftover pre-open query missing its deadline while the probe is
    # deciding must NOT flap the state or free the probe slot
    b.record_miss_locked(now=11.5, probe=False)
    assert b.state == "half_open" and b.probe_inflight
    ok3, _ = b.admit_locked(now=11.6)
    assert not ok3  # still exactly one probe
    # the PROBE misses: straight back to OPEN with a fresh cooldown
    b.record_miss_locked(now=12.0, probe=True)
    assert b.state == OPEN and b.open_until == 22.0 and b.opens == 2
    # next window's probe succeeds: CLOSED
    ok, _ = b.admit_locked(now=23.0)
    assert ok
    b.record_success_locked()
    assert b.state == CLOSED and b.closes == 1


# ---------------------------------------------------------------------------
# drain-rate retry-after
# ---------------------------------------------------------------------------
def test_retry_after_derives_from_observed_drain_rate():
    """depth/drain-rate, not a constant: a tenant that drains 10/s with
    4 queued is told ~0.5s; one with no completion history falls back
    to the service-time estimate."""
    t = TenantState("t", TenantPolicy(), CircuitBreaker(5, 5.0), 10.0)
    # no history: fallback wins
    assert t.retry_after_locked(fallback_s=0.123, now=100.0) == 0.123
    # 10 completions over the last second -> ~10/s
    for i in range(10):
        t.completions.append(99.0 + 0.1 * (i + 1))
    t.queue.extend(range(4))
    ra = t.retry_after_locked(fallback_s=0.123, now=100.0)
    assert ra == pytest.approx(5 / 10.0, rel=0.25)
    # an old burst outside the window no longer counts
    t.completions.clear()
    t.completions.extend([1.0, 1.1, 1.2])
    assert t.retry_after_locked(fallback_s=0.5, now=100.0) == 0.5


def test_rejection_retry_after_reflects_load(env):
    """Integration: after the server observed a drain rate, a full-queue
    rejection's retry-after scales with the tenant's depth."""
    session, hs, src, batch = env
    server = QueryServer(session, ServeConfig(max_workers=1, max_queue=4))
    key = int(batch.columns["k"].data[0])
    for _ in range(3):
        server.submit(_lookup(session, src, key)).result(timeout=120)
    # stop draining, then fill to the global cap
    with server._cond:
        paused_rate = server._tenants["default"].drain_rate_locked()
    assert paused_rate is not None and paused_rate > 0
    server.close()
    server2 = QueryServer(
        session, ServeConfig(max_workers=1, max_queue=2, autostart=False)
    )
    for i in range(2):
        server2.submit(_lookup(session, src, i))
    with pytest.raises(AdmissionRejected) as exc:
        server2.submit(_lookup(session, src, 5))
    assert exc.value.reason == "queue_full"
    assert exc.value.retry_after_s > 0
    server2.start()
    server2.close(timeout_s=120)


# ---------------------------------------------------------------------------
# load-shed ladder
# ---------------------------------------------------------------------------
def test_shed_ladder_rejects_lowest_weight_then_disables_widening(env):
    session, hs, src, batch = env
    session.conf.set(f"{C.SERVE_TENANT_PREFIX}.gold.weight", 4)
    session.conf.set(f"{C.SERVE_TENANT_PREFIX}.bronze.weight", 1)
    server = QueryServer(
        session, ServeConfig(max_workers=1, max_queue=16, autostart=False)
    )
    key = int(batch.columns["k"].data[0])
    # both tenants known to the server, depth below the high-water mark
    server.submit(_lookup(session, src, key), tenant="bronze")
    for i in range(10):
        server.submit(_lookup(session, src, key), tenant="gold")
    assert server.stats()["overload"]["shed_stage"] == 0
    # stage 1 (depth >= 0.75*16=12): lowest-weight tenants shed first
    server.submit(_lookup(session, src, key), tenant="gold")
    assert server.stats()["overload"]["shed_stage"] == 1
    with pytest.raises(AdmissionRejected) as exc:
        server.submit(_lookup(session, src, key), tenant="bronze")
    assert exc.value.reason == "shed_lowweight"
    assert metrics.counter("serve.shed.lowweight") >= 1
    # the high-weight tenant still admits at stage 1
    server.submit(_lookup(session, src, key), tenant="gold")
    # stage 2 (depth >= 0.9*16=14.4 -> 15): widening disabled
    for i in range(2):
        server.submit(_lookup(session, src, key), tenant="gold")
    over = server.stats()["overload"]
    assert over["shed_stage"] == 2
    assert over["batch_widening"] is False
    # stage 2 still admits high-weight work until the global cap
    server.submit(_lookup(session, src, key), tenant="gold")
    with pytest.raises(AdmissionRejected) as exc2:
        server.submit(_lookup(session, src, key), tenant="gold")
    assert exc2.value.reason == "queue_full"
    server.start()
    server.close(timeout_s=300)


# ---------------------------------------------------------------------------
# cancel
# ---------------------------------------------------------------------------
def test_cancel_withdraws_queued_query(env):
    session, hs, src, batch = env
    server = QueryServer(session, ServeConfig(max_workers=1, autostart=False))
    key = int(batch.columns["k"].data[0])
    keep1 = server.submit(_lookup(session, src, key))
    victim = server.submit(_lookup(session, src, key))
    keep2 = server.submit(_lookup(session, src, key))
    before = metrics.counter("serve.cancelled")
    assert victim.cancel() is True
    assert victim.cancel() is False  # idempotent: already resolved
    with pytest.raises(QueryCancelled):
        victim.result(timeout=5)
    assert metrics.counter("serve.cancelled") == before + 1
    server.start()
    assert keep1.result(timeout=120) is not None
    assert keep2.result(timeout=120) is not None
    stats = server.stats()
    assert stats["cancelled"] == 1
    assert stats["completed"] == 2
    assert stats["tenants"]["default"]["cancelled"] == 1
    # conservation: every submission resolved exactly one way
    assert stats["submitted"] == stats["completed"] + stats["cancelled"]
    server.close()


def test_cancel_races_worker_dispatch_exactly_one_wins(env):
    """N producers cancel while workers drain: for every ticket, the
    cancel() verdict and the terminal outcome must agree — True iff
    result() raises QueryCancelled — and the counters conserve."""
    session, hs, src, batch = env
    server = QueryServer(session, ServeConfig(max_workers=4, max_queue=256))
    keys = [int(batch.columns["k"].data[i * 7 % N_ROWS]) for i in range(48)]
    tickets = [server.submit(_lookup(session, src, k)) for k in keys]
    verdicts = [None] * len(tickets)

    def canceller(lo, hi):
        for i in range(lo, hi):
            verdicts[i] = tickets[i].cancel()

    threads = [
        threading.Thread(target=canceller, args=(i * 12, (i + 1) * 12))
        for i in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    cancelled = completed = 0
    for tk, v in zip(tickets, verdicts):
        try:
            tk.result(timeout=120)
            outcome_cancelled = False
            completed += 1
        except QueryCancelled:
            outcome_cancelled = True
            cancelled += 1
        assert v is outcome_cancelled, "cancel verdict disagrees with outcome"
    stats = server.stats()
    assert stats["cancelled"] == cancelled
    assert stats["completed"] == completed
    assert stats["submitted"] == cancelled + completed
    server.close()


# ---------------------------------------------------------------------------
# client retry helper
# ---------------------------------------------------------------------------
def test_submit_with_retry_backs_off_and_succeeds(env):
    session, hs, src, batch = env
    server = QueryServer(
        session, ServeConfig(max_workers=1, max_queue=2, autostart=False)
    )
    key = int(batch.columns["k"].data[0])
    for i in range(2):
        server.submit(_lookup(session, src, key))
    delays = []

    def fake_sleep(s):
        delays.append(s)
        server.start()  # the queue drains during the "sleep"
        with server._cond:
            while server._global_depth_locked() > 0:
                server._cond.wait(0.05)
        time.sleep(0.05)

    before = metrics.counter("serve.client.retry")
    t = submit_with_retry(server, _lookup(session, src, key), sleep=fake_sleep)
    assert t.result(timeout=120) is not None
    assert len(delays) == 1 and delays[0] > 0
    assert metrics.counter("serve.client.retry") == before + 1
    server.close()


def test_submit_with_retry_exhausts_against_closed_queue(env):
    session, hs, src, batch = env
    from hyperspace_tpu.reliability.retry import RetryPolicy

    server = QueryServer(
        session, ServeConfig(max_workers=1, max_queue=1, autostart=False)
    )
    server.submit(_lookup(session, src, 1))
    slept = []
    with pytest.raises(AdmissionRejected):
        submit_with_retry(
            server,
            _lookup(session, src, 2),
            policy=RetryPolicy(max_attempts=3, base_delay_s=0.001),
            sleep=slept.append,
        )
    assert len(slept) == 2  # attempts-1 sleeps, then the final rejection
    assert metrics.counter("serve.client.exhausted") >= 1
    server.start()
    server.close(timeout_s=120)


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------
def test_stats_and_explain_name_tenant_and_pinned_version(env):
    session, hs, src, batch = env
    server = QueryServer(session, ServeConfig(max_workers=1))
    key = int(batch.columns["k"].data[0])
    q = _lookup(session, src, key)
    t = server.submit(q, tenant="analytics")
    assert t.result(timeout=120) is not None
    assert t.tenant == "analytics"
    assert t.pinned_log_version and t.pinned_log_version[0][0] == "midx"
    snap = server.stats()["tenants"]["analytics"]
    assert snap["completed"] == 1
    assert "latency_p50_ms" in snap and "latency_p99_ms" in snap
    assert snap["breaker"]["state"] == CLOSED
    counters = server.stats()["serve_counters"]
    assert counters["submitted"] >= 1 and counters["completed"] >= 1
    out = hs.explain(q, verbose=True)
    assert "Tenant: analytics" in out
    assert "Pinned log version" in out and "midx" in out
    server.close()


# ---------------------------------------------------------------------------
# soak: concurrent ingest + refresh + mixed-tenant bursts + device loss
# ---------------------------------------------------------------------------
def test_soak_mixed_tenant_burst_with_refresh_and_device_loss(env, monkeypatch):
    """The acceptance scenario: 3 weighted
    tenants burst through the server while a refresh lands mid-burst and
    the device dies once mid-batch. Invariants: every ticket RESOLVES;
    every completed result matches the pre- or post-refresh snapshot
    WHOLESALE; no tenant starves; counters conserve; the server is
    degraded (host-latched) but still answering afterwards."""
    from hyperspace_tpu.exec import hbm_cache as hc

    session, hs, src, batch = env
    session.conf.set(C.INDEX_HYBRID_SCAN_ENABLED, True)
    for name, w in (("bronze", 1), ("silver", 2), ("gold", 4)):
        session.conf.set(f"{C.SERVE_TENANT_PREFIX}.{name}.weight", w)
    keys = [int(batch.columns["k"].data[i * 13 % N_ROWS]) for i in range(12)]
    # pre/post-refresh oracles, computed serially before the storm
    pre = {k: _rows(_lookup(session, src, k).collect()) for k in keys}
    appended = _source(3000, seed=9)
    post = {}
    for k in keys:
        extra = [
            (int(k), int(v))
            for kk, v in zip(
                appended.columns["k"].data.tolist(),
                appended.columns["v"].data.tolist(),
            )
            if kk == k
        ]
        post[k] = sorted(pre[k] + extra)

    # ONE injected device loss: the first stacked dispatch raises out of
    # the device call; later calls run the real kernel (by then the
    # server has latched host anyway)
    real = hc.HbmIndexCache.block_counts_batch
    state = {"fired": False}

    def flaky(self, table, predicates, prepared=None, metric_ns="serve.batch"):
        if not state["fired"]:
            state["fired"] = True
            raise RuntimeError("UNAVAILABLE: device lost mid-batch")
        return real(self, table, predicates, prepared, metric_ns)

    monkeypatch.setattr(hc.HbmIndexCache, "block_counts_batch", flaky)

    metrics.reset()
    server = QueryServer(
        session, ServeConfig(max_workers=3, max_queue=256, autostart=False)
    )
    # deterministic device-loss phase: a compatible burst queued on the
    # paused server coalesces into the FIRST dispatch, which is exactly
    # where the loss is injected — the latch fires mid-batch with the
    # whole burst in flight, then the concurrent storm runs host-latched
    burst = [
        server.submit(_lookup(session, src, keys[0]), tenant=t)
        for t in ("bronze", "silver", "gold")
        for _ in range(3)
    ]
    results = {}  # (tenant, i) -> rows or exception
    lock = threading.Lock()
    start_gate = threading.Event()

    def producer(tenant, rounds):
        start_gate.wait(10)
        for i in range(rounds):
            k = keys[(i + rounds) % len(keys)]
            try:
                t = submit_with_retry(
                    server, _lookup(session, src, k), tenant=tenant
                )
                rows = _rows(t.result(timeout=300))
                with lock:
                    results[(tenant, i)] = (k, rows)
            except Exception as e:  # noqa: BLE001 - classified below
                with lock:
                    results[(tenant, i)] = (k, e)

    def refresher():
        start_gate.wait(10)
        time.sleep(0.05)  # land mid-burst
        parquet_io.write_parquet(src / "part-append.parquet", appended)
        hs.refresh_index("midx", C.REFRESH_MODE_INCREMENTAL)

    threads = [
        threading.Thread(target=producer, args=("bronze", 10)),
        threading.Thread(target=producer, args=("silver", 14)),
        threading.Thread(target=producer, args=("gold", 18)),
        threading.Thread(target=refresher),
    ]
    server.start()
    # the injected loss resolved the whole burst from the host, exact
    for t in burst:
        assert _rows(t.result(timeout=300)) == pre[keys[0]]
    assert state["fired"], "device loss never injected"
    for t in threads:
        t.start()
    start_gate.set()
    for t in threads:
        t.join(300)
        assert not t.is_alive(), "soak thread hung"

    # (a) every ticket resolved — and every failure is a classified
    # serving error, never a hang (join asserted above)
    per_tenant_completed = {"bronze": 0, "silver": 0, "gold": 0}
    for (tenant, _i), (k, out) in results.items():
        if isinstance(out, Exception):
            assert isinstance(out, (AdmissionRejected, QueryCancelled)), out
            continue
        per_tenant_completed[tenant] += 1
        # (b) wholesale snapshot: pre- or post-refresh rows, never a mix
        assert out in (pre[k], post[k]), (
            f"torn snapshot for key {k}: {out[:4]}..."
        )
    # (c) no starvation: every tenant completed work through the storm
    for tenant, n in per_tenant_completed.items():
        assert n > 0, f"{tenant} starved"
    stats = server.stats()
    # the injected loss latched the server host-side, exactly once
    assert stats["degraded"] is True
    assert metrics.counter("serve.degraded") == 1
    # counter conservation across the whole storm
    assert stats["submitted"] == (
        stats["completed"] + stats["failed"] + stats["cancelled"]
    )
    # still serving after the storm, host-latched
    t = server.submit(_lookup(session, src, keys[0]))
    assert _rows(t.result(timeout=120)) in (pre[keys[0]], post[keys[0]])
    server.close()
