"""Failure injection for the subtle protocols (round-2 verdict next #6):

(a) the multihost vocab-union's unhappy branches — the stale-cache retry
    loop actually retrying, its timeout raising cleanly, and a peer dying
    before the barrier surfacing as a clean error on the survivor (never
    a hang, never a corrupted union);
(b) a streaming index build KILLED mid-spill (SIGKILL, no teardown):
    the log is stuck in CREATING, further actions refuse, ``cancel()``
    recovers to the last stable state AND garbage-collects the orphaned
    ``.spill`` scratch, and a rebuild then succeeds.
(c) the query server's unhappy paths (serve/): deadline expiry while
    queued, queue-full admission rejection, and a device that wedges
    MID-SERVE — the failed batch must still answer correctly from the
    host engine, the server must latch degraded, and no test may sleep
    on a real 120 s device timeout (all injections are in-process).
"""

import os
import pickle
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.ops.build import unify_vocabs_shared_storage
from hyperspace_tpu.storage.columnar import Column, ColumnarBatch
from hyperspace_tpu.telemetry.metrics import metrics

REPO = Path(__file__).resolve().parent.parent


def _string_batch(values):
    return ColumnarBatch.from_pydict(
        {"s": np.array(values, dtype=object)}, {"s": "string"}
    )


def test_vocab_union_stale_cache_retry_fires(monkeypatch):
    """Peer file appears LATE (NFS-style staleness): the retry loop polls
    until it lands and the union is still exact. EVENT-based
    coordination: the peer file is written only after the reader's retry
    counter has actually fired — the previous fixed 0.4 s sleep raced
    the reader under CI load (a slow first poll meant the file was
    already there and no retry ever happened, failing the >= 1
    assertion on exactly the runs that were busiest)."""
    import tempfile

    scratch = Path(tempfile.mkdtemp())
    batch = _string_batch([b"aa", b"cc", b"aa"])
    metrics.reset()

    retried = threading.Event()
    real_incr = metrics.incr

    def incr_hook(name, by=1):
        real_incr(name, by)
        if name == "build.multihost.vocab_stale_retry":
            retried.set()

    monkeypatch.setattr(metrics, "incr", incr_hook)

    def late_peer():
        # wait for the RETRY, not a wall-clock guess: the file must land
        # only after the reader has observed at least one miss
        assert retried.wait(30.0)
        (scratch / ".late.tmp").write_bytes(
            pickle.dumps({"s": np.array([b"bb", b"dd"], dtype=object)})
        )
        (scratch / ".late.tmp").replace(scratch / "vocab-00001.pkl")

    t = threading.Thread(target=late_peer, daemon=True)
    t.start()
    out = unify_vocabs_shared_storage(
        batch, scratch, barrier=lambda: None, process_index=0,
        process_count=2, timeout_s=60.0,
    )
    t.join()
    assert metrics.counter("build.multihost.vocab_stale_retry") >= 1
    assert out.columns["s"].vocab.tolist() == [b"aa", b"bb", b"cc", b"dd"]
    assert out.columns["s"].to_values().tolist() == ["aa", "cc", "aa"]


def test_vocab_union_timeout_raises_cleanly():
    """A peer that never writes must surface as FileNotFoundError at the
    deadline — not an infinite poll."""
    import tempfile

    scratch = Path(tempfile.mkdtemp())
    batch = _string_batch([b"x"])
    t0 = time.monotonic()
    with pytest.raises(FileNotFoundError):
        unify_vocabs_shared_storage(
            batch, scratch, barrier=lambda: None, process_index=0,
            process_count=2, timeout_s=0.3,
        )
    assert time.monotonic() - t0 < 5.0


_UNIFY_WORKER = r"""
import pickle, sys, time
from pathlib import Path
import numpy as np
sys.path.insert(0, sys.argv[4])
from hyperspace_tpu.storage.columnar import ColumnarBatch
from hyperspace_tpu.ops.build import unify_vocabs_shared_storage

scratch = Path(sys.argv[1]); pid = int(sys.argv[2]); mode = sys.argv[3]

def file_barrier(name="b0", timeout=3.0):
    # shared-storage barrier: write my marker, wait for every peer's.
    # A dead peer => timeout => RuntimeError (clean error, never a hang).
    (scratch / f".bar-{name}-{pid}").touch()
    deadline = time.monotonic() + timeout
    while True:
        if all((scratch / f".bar-{name}-{p}").exists() for p in range(2)):
            return
        if time.monotonic() >= deadline:
            raise RuntimeError(f"barrier {name}: peer missing")
        time.sleep(0.02)

batch = ColumnarBatch.from_pydict(
    {"s": np.array([b"p%d" % pid, b"zz"], dtype=object)}, {"s": "string"}
)
if mode == "die-before-barrier":
    # write the vocab file (the protocol's first step), then die hard
    import os
    payload = {"s": batch.columns["s"].vocab}
    tmp = scratch / f".vocab-{pid:05d}.tmp"
    tmp.write_bytes(pickle.dumps(payload))
    tmp.replace(scratch / f"vocab-{pid:05d}.pkl")
    os._exit(9)

calls = {"n": 0}
def barrier():
    calls["n"] += 1
    file_barrier(f"b{calls['n']}")

out = unify_vocabs_shared_storage(
    batch, scratch, barrier=barrier, process_index=pid, process_count=2,
    timeout_s=3.0,
)
print("UNION:" + ",".join(v.decode() for v in out.columns["s"].vocab))
"""


def test_peer_death_mid_barrier_errors_survivor_cleanly(tmp_path):
    """Process 1 dies after writing its vocab but BEFORE entering the
    barrier; process 0 must get a clean barrier error within its timeout
    — not hang, not fabricate a partial union."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _UNIFY_WORKER, str(tmp_path), str(pid),
             "die-before-barrier" if pid == 1 else "normal", str(REPO)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        )
        for pid in range(2)
    ]
    outs = [p.communicate(timeout=60)[0].decode(errors="replace") for p in procs]
    assert procs[1].returncode == 9
    assert procs[0].returncode != 0
    assert "barrier" in outs[0] and "peer missing" in outs[0]
    assert "UNION:" not in outs[0]  # no partial union fabricated


def test_both_alive_union_succeeds_via_same_barrier(tmp_path):
    """Control for the test above: the same worker + barrier with both
    processes alive produces the exact union on both."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _UNIFY_WORKER, str(tmp_path), str(pid),
             "normal", str(REPO)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        )
        for pid in range(2)
    ]
    outs = [p.communicate(timeout=60)[0].decode(errors="replace") for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "UNION:p0,p1,zz" in out


_KILL_BUILD_WORKER = r"""
import os, sys, time
import numpy as np
sys.path.insert(0, sys.argv[2])
# Pin CPU at the config level as well: a chip-side compile can outlast
# this worker's kill timeout, and the parent test process may hold the
# chip.
import jax
jax.config.update("jax_platforms", "cpu")
ws = sys.argv[1]
import pyarrow as pa, pyarrow.parquet as pq
rng = np.random.default_rng(0)
n = 400_000
os.makedirs(f"{ws}/src", exist_ok=True)
pq.write_table(pa.table({"k": rng.integers(0, 10**6, n).astype(np.int64),
                         "v": rng.integers(0, 100, n).astype(np.int64)}),
               f"{ws}/src/a.parquet")
from hyperspace_tpu import constants as C
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.index.index_config import IndexConfig
from hyperspace_tpu.session import HyperspaceSession
from hyperspace_tpu.index import stream_builder

# suicide mid-spill: the third spilled run SIGKILLs the process — no
# teardown, no atexit, exactly a crashed builder
real = stream_builder.StreamingIndexWriter._spill_run_at
count = {"n": 0}
def killer(self, *a, **k):
    count["n"] += 1
    if count["n"] >= 3:
        print("KILLING", flush=True)
        os.kill(os.getpid(), 9)
    return real(self, *a, **k)
stream_builder.StreamingIndexWriter._spill_run_at = killer

conf = HyperspaceConf({C.INDEX_SYSTEM_PATH: f"{ws}/indexes",
                       C.INDEX_NUM_BUCKETS: 8,
                       C.BUILD_MODE: C.BUILD_MODE_STREAMING,
                       C.BUILD_CHUNK_ROWS: 1 << 16})
hs = Hyperspace(HyperspaceSession(conf))
df = hs.session.read.parquet(f"{ws}/src")
hs.create_index(df, IndexConfig("victim", ["k"], ["v"]))
print("SHOULD NOT REACH", flush=True)
"""


def test_sigkill_mid_spill_cancel_recovers_and_gcs_spill(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.Popen(
        [sys.executable, "-c", _KILL_BUILD_WORKER, str(tmp_path), str(REPO)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
    )
    out, _ = p.communicate(timeout=240)
    assert p.returncode == -signal.SIGKILL or p.returncode == 137, out.decode()
    assert b"SHOULD NOT REACH" not in out

    # crash artifacts: transient CREATING entry + orphaned spill scratch
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.actions import states
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.session import HyperspaceSession

    conf = HyperspaceConf(
        {
            C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"),
            C.INDEX_NUM_BUCKETS: 8,
        }
    )
    hs = Hyperspace(HyperspaceSession(conf))
    df = hs.session.read.parquet(str(tmp_path / "src"))
    victim_dir = tmp_path / "indexes" / "victim"
    spills = list(victim_dir.glob("v__=*/.spill"))
    assert spills, "expected an orphaned spill dir from the killed build"

    # further modifying actions refuse while stuck in CREATING
    with pytest.raises(HyperspaceException):
        hs.delete_index("victim")
    entry = hs.session.collection_manager._existing_log_manager("victim").get_latest_log()
    assert entry.state == states.CREATING

    # cancel(): log recovered to the last stable state (none -> gone) and
    # the spill scratch is garbage-collected
    hs.cancel("victim")
    entry = hs.session.collection_manager._existing_log_manager("victim").get_latest_log()
    assert entry.state == states.DOESNOTEXIST
    assert not list(victim_dir.glob("v__=*/.spill"))

    # and the index can be rebuilt cleanly afterwards
    hs.create_index(df, IndexConfig("victim", ["k"], ["v"]))
    q = hs.session.read.parquet(str(tmp_path / "src"))
    hs.session.enable_hyperspace()
    from hyperspace_tpu.plan.expr import col

    key = int(np.random.default_rng(0).integers(0, 10**6, 400_000)[0])
    got = q.filter(col("k") == key).select("k", "v").collect()
    assert got.num_rows >= 1


# ---------------------------------------------------------------------------
# (c) query-server fault injection (serve/): deadline expiry, queue-full
#     rejection, wedged device mid-serve. Every failure is injected
#     in-process — no test waits on a real device timeout.
# ---------------------------------------------------------------------------


@pytest.fixture
def serve_env(tmp_path, monkeypatch):
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.exec.hbm_cache import hbm_cache
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.session import HyperspaceSession
    from hyperspace_tpu.storage import parquet_io

    monkeypatch.setenv("HYPERSPACE_TPU_HBM", "force")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_MIN_ROWS", "1")
    hbm_cache.reset()
    rng = np.random.default_rng(2)
    batch = ColumnarBatch.from_pydict(
        {
            "k": rng.integers(0, 5000, 40_000).astype(np.int64),
            "v": rng.integers(0, 100, 40_000).astype(np.int64),
        }
    )
    src = tmp_path / "data"
    src.mkdir()
    parquet_io.write_parquet(src / "part-0.parquet", batch)
    conf = HyperspaceConf(
        {C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"), C.INDEX_NUM_BUCKETS: 4}
    )
    session = HyperspaceSession(conf)
    hs = Hyperspace(session)
    hs.create_index(
        session.read.parquet(str(src)), IndexConfig("fidx", ["k"], ["v"])
    )
    session.enable_hyperspace()
    assert hs.prefetch_index("fidx")
    yield session, src, batch
    hbm_cache.reset()


def _serve_lookup(session, src, key):
    from hyperspace_tpu.plan.expr import col, lit

    return (
        session.read.parquet(str(src))
        .filter(col("k") == lit(int(key)))
        .select("k", "v")
    )


def test_serve_deadline_expiry_fails_queued_query_without_executing(serve_env):
    from hyperspace_tpu.serve import DeadlineExceeded, QueryServer, ServeConfig

    session, src, batch = serve_env
    server = QueryServer(session, ServeConfig(max_workers=1, autostart=False))
    # queued on a PAUSED server with a deadline that lapses before any
    # worker exists: the query must fail with DeadlineExceeded at drain
    # time, without ever executing
    doomed = server.submit(
        _serve_lookup(session, src, batch.columns["k"].data[0]),
        deadline_s=0.01,
    )
    live = server.submit(_serve_lookup(session, src, batch.columns["k"].data[1]))
    time.sleep(0.05)
    server.start()
    with pytest.raises(DeadlineExceeded):
        doomed.result(timeout=60)
    assert doomed.started_at is None  # never executed — queue time only
    assert live.result(timeout=60).num_rows >= 0
    assert server.stats()["deadline_missed"] == 1
    assert metrics.counter("serve.deadline_missed") >= 1
    server.close()


def test_serve_queue_full_rejection_is_backpressure_not_latency(serve_env):
    from hyperspace_tpu.serve import AdmissionRejected, QueryServer, ServeConfig

    session, src, batch = serve_env
    server = QueryServer(
        session, ServeConfig(max_workers=1, max_queue=2, autostart=False)
    )
    for i in range(2):
        server.submit(_serve_lookup(session, src, i))
    t0 = time.monotonic()
    with pytest.raises(AdmissionRejected) as exc:
        server.submit(_serve_lookup(session, src, 2))
    # rejection is IMMEDIATE (admission control, not a queue timeout) and
    # carries what a load balancer needs: depth + a retry-after estimate
    assert time.monotonic() - t0 < 1.0
    assert exc.value.queue_depth == 2
    assert exc.value.retry_after_s > 0
    assert server.stats()["shed"] == 1
    server.start()
    server.close(timeout_s=120)


def test_serve_wedged_device_mid_serve_degrades_and_answers_from_host(
    serve_env, monkeypatch
):
    from hyperspace_tpu.exec import hbm_cache as hc
    from hyperspace_tpu.serve import QueryServer, ServeConfig

    session, src, batch = serve_env
    keys = [int(batch.columns["k"].data[i]) for i in range(8)]
    queries = [_serve_lookup(session, src, k) for k in keys]
    serial = [q.collect() for q in queries]

    # fault injection: the batched device dispatch raises out of the jax
    # call, not a clean None
    def wedged(self, table, predicates, prepared=None, metric_ns="serve.batch"):
        raise RuntimeError("DEADLINE_EXCEEDED: device wedged")

    monkeypatch.setattr(hc.HbmIndexCache, "block_counts_batch", wedged)
    metrics.reset()
    # ONE worker so the whole burst lands in the wedged batch: a second
    # worker would race a query down the single-query device scan, find
    # the just-dropped table missing, and note_touch a background
    # repopulation (correct in production — the injection wedges only the
    # batch entry point, not the device — but it makes the "nothing
    # resident remains" assertion below racy)
    server = QueryServer(
        session, ServeConfig(max_workers=1, autostart=False)
    )
    tickets = [server.submit(q) for q in queries]
    server.start()
    results = [t.result(timeout=120) for t in tickets]

    # no error escaped to any caller: the failed batch re-ran host-side
    # with identical results
    def rows(b):
        return sorted(
            zip(b.columns["k"].data.tolist(), b.columns["v"].data.tolist())
        )

    for s, r in zip(serial, results):
        assert rows(s) == rows(r)
    stats = server.stats()
    assert stats["degraded"] is True
    assert "wedged" in stats["degraded_reason"]
    assert stats["batch_dispatches"] == 0  # the device batch never landed
    assert metrics.counter("serve.degraded") == 1
    # the wedged table was dropped: nothing resident remains to retry
    assert hc.hbm_cache.snapshot()["tables"] == 0
    # later queries keep being served (host-latched), still correct
    later = server.submit(_serve_lookup(session, src, keys[0]))
    assert rows(later.result(timeout=120)) == rows(serial[0])
    assert server.degraded is True
    server.close()


# ---------------------------------------------------------------------------
# (d) delta residency: device loss DURING background delta population
#     must leave the hybrid query on the host union path with parity
#     intact and the resident registry clean; a reset() between schedule
#     and registration (the epoch guard) must refuse the stale region.
# ---------------------------------------------------------------------------


@pytest.fixture
def hybrid_env(tmp_path, monkeypatch):
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.exec.hbm_cache import hbm_cache
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.session import HyperspaceSession
    from hyperspace_tpu.storage import parquet_io

    monkeypatch.setenv("HYPERSPACE_TPU_HBM", "force")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_MIN_ROWS", "1")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_MAX_BLOCK_FRAC", "1.0")
    hbm_cache.reset()
    rng = np.random.default_rng(4)
    batch = ColumnarBatch.from_pydict(
        {
            "k": rng.integers(0, 2000, 30_000).astype(np.int64),
            "v": rng.integers(0, 100, 30_000).astype(np.int64),
        }
    )
    src = tmp_path / "data"
    src.mkdir()
    parquet_io.write_parquet(src / "part-0.parquet", batch)
    conf = HyperspaceConf(
        {
            C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"),
            C.INDEX_NUM_BUCKETS: 4,
            C.INDEX_HYBRID_SCAN_ENABLED: True,
        }
    )
    session = HyperspaceSession(conf)
    hs = Hyperspace(session)
    hs.create_index(
        session.read.parquet(str(src)), IndexConfig("hfi", ["k"], ["v"])
    )
    session.enable_hyperspace()
    assert hs.prefetch_index("hfi")
    # the append that makes every query hybrid
    ap = ColumnarBatch.from_pydict(
        {
            "k": rng.integers(0, 2000, 800).astype(np.int64),
            "v": rng.integers(0, 100, 800).astype(np.int64),
        }
    )
    parquet_io.write_parquet(src / "part-append.parquet", ap)
    yield session, src, batch
    hbm_cache.reset()


def test_device_loss_during_delta_population_keeps_host_path_and_clean_registry(
    hybrid_env, monkeypatch
):
    from hyperspace_tpu import ops
    from hyperspace_tpu.exec.hbm_cache import hbm_cache
    from hyperspace_tpu.plan.expr import col, lit

    session, src, batch = hybrid_env
    key = int(batch.columns["k"].data[3])
    q = lambda: (  # noqa: E731
        session.read.parquet(str(src))
        .filter(col("k") == lit(key))
        .select("k", "v")
    )
    session.disable_hyperspace()
    off = q().collect()
    session.enable_hyperspace()

    # fault injection: the delta upload's materializing fence raises out
    # of the device readback
    real_fence = ops.fence_chain

    def dead_fence(arrays):
        raise RuntimeError("DEADLINE_EXCEEDED: device wedged")

    monkeypatch.setattr(ops, "fence_chain", dead_fence)
    metrics.reset()
    # first hybrid query: base resident, delta missing -> schedules the
    # background population (which will die on the fence) and serves
    # THIS query from the host union — parity must hold
    on1 = q().collect()
    assert sorted(on1.columns["v"].data.tolist()) == sorted(
        off.columns["v"].data.tolist()
    )
    hbm_cache.wait_background(timeout_s=30.0)
    assert metrics.counter("hbm.delta.transfer_error") >= 1
    snap = hbm_cache.snapshot()
    assert snap["deltas"] == 0, "half-built delta leaked into the registry"
    assert snap["tables"] == 1, "base table must survive a delta failure"
    assert metrics.counter("scan.path.resident_hybrid") == 0
    # the failure is TRANSIENT (not memoized): with the device healthy
    # again, the next touch repopulates and the query re-fuses
    monkeypatch.setattr(ops, "fence_chain", real_fence)
    on2 = q().collect()  # schedules a fresh population
    assert sorted(on2.columns["v"].data.tolist()) == sorted(
        off.columns["v"].data.tolist()
    )
    hbm_cache.wait_background(timeout_s=30.0)
    assert hbm_cache.snapshot()["deltas"] == 1
    on3 = q().collect()
    assert metrics.counter("scan.path.resident_hybrid") == 1
    assert sorted(on3.columns["v"].data.tolist()) == sorted(
        off.columns["v"].data.tolist()
    )


def test_reset_epoch_guard_refuses_stale_delta_registration(
    hybrid_env, monkeypatch
):
    """A reset() between scheduling and registration must win: the slow
    background build's region lands against a bumped epoch and is
    refused (the same guard the base tables use)."""
    from hyperspace_tpu.exec.hbm_cache import hbm_cache
    from hyperspace_tpu.plan.expr import col, lit
    from hyperspace_tpu.plan.ir import Union
    from hyperspace_tpu.plan.rules.hybrid_scan import parse_hybrid_union
    from hyperspace_tpu.storage import parquet_io as pio

    session, src, batch = hybrid_env
    key = int(batch.columns["k"].data[3])
    q = (
        session.read.parquet(str(src))
        .filter(col("k") == lit(key))
        .select("k", "v")
    )
    union = q.optimized_plan().collect(lambda n: isinstance(n, Union))[0]
    info = parse_hybrid_union(union)
    table = hbm_cache.resident_for(info.entry.content.files(), ["k"])
    assert table is not None

    gate = threading.Event()
    release = threading.Event()
    real_read = pio.read_relation

    def slow_read(*a, **kw):
        gate.set()
        assert release.wait(30.0)
        return real_read(*a, **kw)

    monkeypatch.setattr(pio, "read_relation", slow_read)
    hbm_cache.note_touch_delta(
        table, info.appended, info.relation, list(info.user_cols), ()
    )
    assert gate.wait(10.0)  # the background build is inside the read
    hbm_cache.reset()  # bumps the epoch while the build is in flight
    release.set()
    hbm_cache.wait_background(timeout_s=30.0)
    assert hbm_cache.snapshot()["deltas"] == 0, (
        "stale delta registered across a reset()"
    )


def test_serve_deviceprobe_latch_degrades_before_any_serve_failure(
    serve_env, monkeypatch
):
    """A wedged device discovered by ANY component (deviceprobe's
    first-touch latch) must route serving host WITHOUT waiting for a
    serve-path failure — the `degraded` property consults the latch."""
    from hyperspace_tpu.serve import QueryServer, ServeConfig
    from hyperspace_tpu.utils import deviceprobe

    session, src, batch = serve_env
    monkeypatch.setitem(deviceprobe._FIRST_TOUCH, "ok", False)
    server = QueryServer(session, ServeConfig(max_workers=1, autostart=False))
    assert server.degraded is True
    # queries still answer, host-side
    t = server.submit(_serve_lookup(session, src, batch.columns["k"].data[0]))
    server.start()
    assert t.result(timeout=120).num_rows >= 0
    assert t.batch_size == 1  # host-latched serving never batches
    server.close()


# ---------------------------------------------------------------------------
# (e) storage-flake + crash-litter injection (reliability/): a flaky
#     object store must not fail lifecycle actions (retry absorbs), and
#     the temp files a crashed atomic_create leaves behind must be
#     reported by fsck and swept by recovery.
# ---------------------------------------------------------------------------


def test_flaky_storage_log_rpcs_do_not_fail_lifecycle(tmp_path):
    """Every 2nd log-protocol RPC fails transiently; create + delete +
    restore still succeed end-to-end through the retry layer, and the
    flakes are visible in metrics (not silently absorbed)."""
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.actions import states
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.index.collection_manager import IndexCollectionManager
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.index.log_manager import IndexLogManagerImpl
    from hyperspace_tpu.reliability import FaultInjectingFileSystem, FaultRule
    from hyperspace_tpu.session import HyperspaceSession
    from hyperspace_tpu.storage import parquet_io as pio
    from hyperspace_tpu.storage.columnar import ColumnarBatch
    from hyperspace_tpu.storage.filesystem import PosixFileSystem

    rng = np.random.default_rng(1)
    src = tmp_path / "data"
    src.mkdir()
    pio.write_parquet(
        src / "p0.parquet",
        ColumnarBatch.from_pydict(
            {
                "k": rng.integers(0, 20, 200).astype(np.int64),
                "v": rng.integers(0, 100, 200).astype(np.int64),
            }
        ),
    )
    conf = HyperspaceConf(
        {
            C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"),
            C.INDEX_NUM_BUCKETS: 2,
            C.RELIABILITY_RETRY_BASE_DELAY_SECONDS: 0.001,
            C.RELIABILITY_RETRY_MAX_DELAY_SECONDS: 0.002,
        }
    )
    session = HyperspaceSession(conf)
    hs = Hyperspace(session)
    fault = FaultInjectingFileSystem(
        PosixFileSystem(), [FaultRule(kind="fail", op="*", every=2)]
    )
    orig = IndexCollectionManager._log_manager

    def patched(self, name):
        return IndexLogManagerImpl(
            self.path_resolver.get_index_path(name),
            fs=fault,
            retry_policy=self.conf.retry_policy(),
        )

    IndexCollectionManager._log_manager = patched
    metrics.reset()
    try:
        hs.create_index(
            session.read.parquet(str(src)), IndexConfig("flaky", ["k"], ["v"])
        )
        hs.delete_index("flaky")
        hs.restore_index("flaky")
    finally:
        IndexCollectionManager._log_manager = orig
    mgr = IndexLogManagerImpl(tmp_path / "indexes" / "flaky")
    assert mgr.get_latest_stable_log().state == states.ACTIVE
    assert metrics.counter("storage.retry.attempts") > 0
    assert metrics.counter("storage.retry.exhausted") == 0


def test_orphan_tmp_files_reported_by_fsck_and_swept_by_recovery(tmp_path):
    """Satellite: ``.name.tmp.pid.rand`` litter from a crashed
    atomic_create (died between temp-write and link) is reported by
    doctor() and swept when recovery rolls the abandoned writer back."""
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.actions import states as st
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.index.data_manager import IndexDataManagerImpl
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.index.log_manager import IndexLogManagerImpl
    from hyperspace_tpu.reliability import LeaseManager, doctor, maybe_auto_recover
    from hyperspace_tpu.session import HyperspaceSession
    from hyperspace_tpu.storage import parquet_io as pio
    from hyperspace_tpu.storage.columnar import ColumnarBatch
    from hyperspace_tpu.storage.filesystem import PosixFileSystem

    rng = np.random.default_rng(2)
    src = tmp_path / "data"
    src.mkdir()
    pio.write_parquet(
        src / "p0.parquet",
        ColumnarBatch.from_pydict(
            {
                "k": rng.integers(0, 20, 150).astype(np.int64),
                "v": rng.integers(0, 100, 150).astype(np.int64),
            }
        ),
    )
    conf = HyperspaceConf(
        {C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"), C.INDEX_NUM_BUCKETS: 2}
    )
    session = HyperspaceSession(conf)
    hs = Hyperspace(session)
    hs.create_index(session.read.parquet(str(src)), IndexConfig("lit", ["k"], ["v"]))
    idx = tmp_path / "indexes" / "lit"
    log_dir = idx / C.HYPERSPACE_LOG
    mgr = IndexLogManagerImpl(idx)

    # simulate the dead writer: transient entry, expired lease, and the
    # temp file its atomic_create left between temp-write and link
    stuck = mgr.get_latest_log()
    stuck.state = st.REFRESHING
    assert mgr.write_log(stuck.id + 1, stuck)
    lm = LeaseManager(idx, PosixFileSystem())
    held = lm.acquire(duration_s=30.0)
    held._stop.set()
    held._thread.join(timeout=10.0)
    rec = lm.current()
    rec.expires_at_ms = int(time.time() * 1000) - 10_000
    Path(lm._path_of(rec.epoch)).write_text(rec.to_json(), encoding="utf-8")
    litter = log_dir / f".{stuck.id + 2}.tmp.424242.cafebabe"
    litter.write_bytes(b"{ half an entry")
    # crash litter is old by the time recovery runs; the sweep's age
    # guard (which protects a LIVE writer's in-flight temp) must not
    # mistake this for fresh
    old = time.time() - 300
    os.utime(litter, (old, old))

    report = doctor(idx)
    assert any(i.kind == "orphan-temp" for i in report.issues)
    assert any(i.kind == "abandoned-writer" for i in report.issues)

    metrics.reset()
    assert maybe_auto_recover(
        mgr, data_manager=IndexDataManagerImpl(idx), conf=session.conf
    )
    assert not litter.exists(), "recovery must sweep the atomic_create litter"
    assert metrics.counter("recovery.orphan_tmp_swept") >= 1
    assert mgr.get_latest_log().state == st.ACTIVE
    assert doctor(idx).ok


# ---------------------------------------------------------------------------
# (f) oversubscribed-residency fault injection (residency/): device loss
#     MID-WINDOW on the streaming tier and MID-POPULATION on the
#     compressed tier must drop the region cleanly, answer the query
#     host-side (latch), and leave the registry/epoch state consistent.
# ---------------------------------------------------------------------------


@pytest.fixture
def oversub_env(tmp_path, monkeypatch):
    """A table whose raw predicate planes exceed the (shrunken) HBM
    budget — the ladder's streaming shape with windowRows forcing
    multiple windows."""
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.exec.hbm_cache import hbm_cache
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.session import HyperspaceSession
    from hyperspace_tpu.storage import parquet_io

    monkeypatch.setenv("HYPERSPACE_TPU_HBM", "force")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_MIN_ROWS", "1")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_BUDGET_MB", "1")
    monkeypatch.setenv("HYPERSPACE_TPU_RESIDENCY_WINDOW_ROWS", "65536")
    hbm_cache.reset()
    rng = np.random.default_rng(9)
    batch = ColumnarBatch.from_pydict(
        {
            "k": rng.integers(0, 50, 200_000).astype(np.int64),
            "v": rng.integers(0, 1 << 30, 200_000).astype(np.int64),
        }
    )
    src = tmp_path / "data"
    src.mkdir()
    parquet_io.write_parquet(src / "p0.parquet", batch)
    conf = HyperspaceConf(
        {C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"), C.INDEX_NUM_BUCKETS: 2}
    )
    session = HyperspaceSession(conf)
    hs = Hyperspace(session)
    hs.create_index(
        session.read.parquet(str(src)), IndexConfig("ovi", ["k"], ["v"])
    )
    session.enable_hyperspace()
    yield session, hs, src, batch
    hbm_cache.reset()


def test_device_loss_mid_window_drops_region_and_answers_from_host(
    oversub_env, monkeypatch
):
    """The streaming dispatch dies on window 2 of N: the query must still
    answer exactly (host fallback), the streaming table must be dropped
    (no retry against a dead device), the window generation must bump so
    serve batches never span the discontinuity, and the registry must
    hold no half-state."""
    from hyperspace_tpu.exec.hbm_cache import hbm_cache
    from hyperspace_tpu.plan.expr import col, lit
    from hyperspace_tpu.residency import streaming as ST

    session, hs, src, batch = oversub_env
    assert hs.prefetch_index("ovi", ["k", "v"])
    snap = hbm_cache.snapshot_residency()
    assert snap["by_tier"] == {"streaming": 1}
    assert snap["tables"][0]["windows"] >= 3
    table = hbm_cache._tables[0]
    gen0 = table.window_gen

    q = lambda: (  # noqa: E731
        session.read.parquet(str(src))
        .filter((col("k") == lit(7)) & (col("v") >= lit(0)))
        .select("k", "v")
    )
    session.disable_hyperspace()
    off = q().collect()
    session.enable_hyperspace()

    real_upload = ST._upload_window
    calls = {"n": 0}

    def dying_upload(table_, names, w):
        calls["n"] += 1
        if calls["n"] >= 2:  # window 0 uploads fine, the next one dies
            raise RuntimeError("DEADLINE_EXCEEDED: device wedged")
        return real_upload(table_, names, w)

    monkeypatch.setattr(ST, "_upload_window", dying_upload)
    metrics.reset()
    on = q().collect()

    def rows(b):
        return sorted(
            zip(b.columns["k"].data.tolist(), b.columns["v"].data.tolist())
        )

    assert rows(on) == rows(off), "mid-window loss must degrade, not corrupt"
    assert metrics.counter("residency.stream.window_failed") == 1
    assert metrics.counter("scan.resident.device_failed") == 1
    assert table.window_gen == gen0 + 1, "generation must bump on failure"
    snap2 = hbm_cache.snapshot()
    assert snap2["tables"] == 0, "dead streaming table must be dropped"
    assert snap2["deltas"] == 0 and snap2["joins"] == 0

    # healthy again: repopulation restores the streaming path exactly
    monkeypatch.setattr(ST, "_upload_window", real_upload)
    assert hs.prefetch_index("ovi", ["k", "v"])
    metrics.reset()
    again = q().collect()
    assert rows(again) == rows(off)
    assert metrics.counter("scan.path.resident_streaming") == 1


def test_device_loss_mid_compressed_population_keeps_registry_clean(
    oversub_env, monkeypatch
):
    """The compressed build's materializing fence dies (device lost):
    nothing may register, the failure is transient (not memoized), and a
    healthy retry lands the compressed table."""
    from hyperspace_tpu import ops
    from hyperspace_tpu.exec.hbm_cache import hbm_cache
    from hyperspace_tpu.plan.expr import col, lit

    session, hs, src, batch = oversub_env
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_BUDGET_MB", "2")
    monkeypatch.setenv("HYPERSPACE_TPU_RESIDENCY_COMPRESSION", "force")

    real_fence = ops.fence_chain

    def dead_fence(arrays):
        raise RuntimeError("DEADLINE_EXCEEDED: device wedged")

    monkeypatch.setattr(ops, "fence_chain", dead_fence)
    metrics.reset()
    assert not hs.prefetch_index("ovi", ["k", "v"])
    assert metrics.counter("hbm.device_transfer_error") >= 1
    snap = hbm_cache.snapshot()
    assert snap["tables"] == 0, "half-uploaded compressed table leaked"
    # query still answers host-side, exactly
    q = (
        session.read.parquet(str(src))
        .filter(col("k") == lit(3))
        .select("k", "v")
    )
    session.disable_hyperspace()
    off = q.collect()
    session.enable_hyperspace()
    on = q.collect()
    assert sorted(on.columns["v"].data.tolist()) == sorted(
        off.columns["v"].data.tolist()
    )
    # transient: with the device healthy the same build succeeds
    monkeypatch.setattr(ops, "fence_chain", real_fence)
    assert hs.prefetch_index("ovi", ["k", "v"])
    assert hbm_cache.snapshot_residency()["by_tier"] == {"compressed": 1}


def test_reset_epoch_guard_refuses_stale_streaming_registration(
    oversub_env, monkeypatch
):
    """A reset() while a background STREAMING build is in flight must
    win: the build's table lands against a bumped epoch and is refused —
    the same guard the resident tables and delta regions already have
    (HS012's fence discipline at the registry seam)."""
    from hyperspace_tpu.exec.hbm_cache import hbm_cache
    from hyperspace_tpu.plan.expr import col, lit
    from hyperspace_tpu.residency import streaming as ST

    session, hs, src, batch = oversub_env
    gate = threading.Event()
    release = threading.Event()
    real_pack = ST.pack_plain

    def slow_pack(values, spec):
        gate.set()
        assert release.wait(30.0)
        return real_pack(values, spec)

    monkeypatch.setattr(ST, "pack_plain", slow_pack)
    # first query schedules the background streaming build (note_touch);
    # the predicate spans BOTH columns so the touched column set's raw
    # planes exceed the 1 MB budget and the ladder lands on streaming
    q = (
        session.read.parquet(str(src))
        .filter((col("k") == lit(5)) & (col("v") >= lit(0)))
        .select("k", "v")
    )
    q.collect()
    assert gate.wait(10.0), "background build never reached the packer"
    hbm_cache.reset()  # bumps the epoch mid-build
    release.set()
    hbm_cache.wait_background(timeout_s=30.0)
    assert hbm_cache.snapshot()["tables"] == 0, (
        "stale streaming table registered across a reset()"
    )
