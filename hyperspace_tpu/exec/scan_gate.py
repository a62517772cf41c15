"""Measured device-vs-host routing for the scan's predicate mask.

Round-2 verdict weak #2: the scan's device gate was a static constant
(``MIN_DEVICE_ROWS = 1_000_000``) with no evidence the threshold was right
on any given deployment. This module routes the non-resident scan by an
in-process measurement instead. Per padded-size class (pow2 of the file's
row count):

1. the first eligible batch runs the HOST mask, timed;
2. a compile-free LINK check times moving the predicate's column bytes
   H2D plus a mask-sized D2H readback — the device path's unavoidable
   floor. If the link alone exceeds the host mask, the device cannot win
   whatever its kernel speed, and it is ruled out WITHOUT paying the XLA
   compile (a host whose device link is thin);
3. otherwise the next eligible batch runs the device mask (compile
   bearer) and the one after is the timed warm device round; the measured
   winner takes every later batch in that size class.

Verdicts memoize in-process only: each process measures its own.
Batches under ``PROBE_MIN_ROWS`` never probe: at small sizes the probe
itself (a device transfer, potentially a compile) costs more than any
possible win — so they route host unconditionally, which also keeps
small-fixture test runs deterministic.

RESIDENCY-AWARENESS: the gate's link arithmetic prices the per-query
H2D upload — which HBM-resident tables (exec/hbm_cache.py) have already
paid. The scan therefore checks residency BEFORE consulting this gate
and routes resident file sets to the device unconditionally; the gate
only arbitrates the non-resident (upload-per-query) path. This is
delta-aware: a hybrid scan whose base AND appended delta are resident
(exec/hbm_cache DeltaRegion) bypasses the gate the same way — its
appended side has no per-query upload left to price either — recorded
via ``note_resident_bypass`` so the bypass is observable per kind.

Reference parity: Spark has no such gate (the JVM executes everything);
this is TPU-native routing policy, observable via ``scan.gate.*`` metrics
and the ``snapshot()`` the bench records (BASELINE north star: prove what
the device path delivers, even where routing rightly prefers host).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import numpy as np

from ..telemetry.metrics import metrics
from ..utils.intmath import next_pow2

# Below this row count the gate does not even probe: the host mask is
# sub-millisecond and a device probe would cost more than it could save.
PROBE_MIN_ROWS = 1 << 16


class ScanGate:
    """Per-(platform, padded-size) measured winner for the mask engine."""

    def __init__(self) -> None:
        self._state: Dict[int, dict] = {}  # n_pad -> probe state
        self._lock = threading.Lock()

    # -- decision ------------------------------------------------------------
    def decide(self, n_rows: int) -> str:
        """One of: host | device | probe-host | probe-device-compile |
        probe-device-timed. Probe stages advance as results arrive."""
        if n_rows < PROBE_MIN_ROWS:
            return "host"
        n_pad = next_pow2(n_rows)
        with self._lock:
            st = self._state.setdefault(n_pad, {})
            if "winner" in st:
                return st["winner"]
            if "host_s" not in st:
                return "probe-host"
            if "link_pending" in st:
                # the link probe (which may pay cold backend init) runs on
                # a background thread — queries never stall on it; route
                # host until its verdict lands
                return "host"
            if "compiled" not in st:
                return "probe-device-compile"
            if "device_s" not in st:
                return "probe-device-timed"
        return self._publish(n_pad)

    # -- probe results -------------------------------------------------------
    def record_host(self, n_rows: int, host_s: float, arrays: dict) -> None:
        """Host mask timing; kicks the link check off on a DAEMON thread —
        the first jax transfer of a process can pay seconds of backend
        init, which must never be charged to a user's query (the stall
        the build's init-free cache key exists to avoid). ``arrays`` are
        the predicate's column buffers for the probed batch."""
        n_pad = next_pow2(n_rows)
        with self._lock:
            st = self._state.setdefault(n_pad, {})
            if "host_s" in st:  # another thread probed concurrently
                return
            st["host_s"] = host_s
            st["link_pending"] = True
            t = threading.Thread(
                target=self._link_probe_bg,
                args=(n_pad, dict(arrays), n_rows),
                daemon=True,
                name="scan-gate-link-probe",
            )
            # registered under the lock BEFORE start: a concurrent
            # wait_probe()/snapshot() must never miss the in-flight probe
            st["_probe_thread"] = t
        metrics.record_time("scan.gate.probe_host", host_s)
        _join_bg_threads_at_exit()
        t.start()

    def _link_probe_bg(self, n_pad: int, arrays: dict, n_rows: int) -> None:
        link_s = self._time_link(arrays, n_rows)
        with self._lock:
            st = self._state.setdefault(n_pad, {})
            st.pop("link_pending", None)
            if "winner" in st:
                # a device failure pinned host while this probe was in
                # flight: that verdict stands
                return
            if link_s is None:
                # no usable device: decide host now, don't keep probing
                st["winner"] = "host"
                st["by"] = "no-device"
            else:
                st["link_s"] = link_s
                metrics.record_time("scan.gate.probe_link", link_s)
                if link_s > st.get("host_s", 0.0):
                    st["winner"] = "host"
                    st["by"] = "link"
                    metrics.incr("scan.gate.chose_host_by_link")
                else:
                    return  # link is fast: device probe stages may proceed
        self._count_winner(n_pad)

    def wait_probe(
        self, n_rows: Optional[int] = None, timeout: float = 10.0
    ) -> None:
        """Block until background link probes (for one size class, or all
        when ``n_rows`` is None) have published — tests and benches need
        deterministic state."""
        # snapshot under the lock: a concurrent decide() inserting a new
        # size class while this iterates would raise "dictionary changed
        # size during iteration" (HS010's lock-free-read finding)
        with self._lock:
            if n_rows is not None:
                t = self._state.get(next_pow2(n_rows), {}).get("_probe_thread")
                threads = [t] if t is not None else []
            else:
                threads = [
                    st["_probe_thread"]
                    for st in self._state.values()
                    if "_probe_thread" in st
                ]
        for t in threads:
            t.join(timeout)

    def record_device_compiled(self, n_rows: int) -> None:
        with self._lock:
            self._state.setdefault(next_pow2(n_rows), {})["compiled"] = True

    def record_device(self, n_rows: int, device_s: float) -> None:
        n_pad = next_pow2(n_rows)
        with self._lock:
            st = self._state.setdefault(n_pad, {})
            st["device_s"] = device_s
        metrics.record_time("scan.gate.probe_device", device_s)
        self._publish(n_pad)

    def record_device_failure(self, n_rows: int) -> None:
        """A device mask raised mid-query: pin this size class to host so
        the failure isn't retried every batch (the query itself already
        fell back to the host mask and succeeded)."""
        n_pad = next_pow2(n_rows)
        with self._lock:
            st = self._state.setdefault(n_pad, {})
            st["winner"] = "host"
            st["by"] = "device-error"
        metrics.incr("scan.gate.device_failed")

    # -- internals -----------------------------------------------------------
    def _publish(self, n_pad: int) -> str:
        with self._lock:
            st = self._state[n_pad]
            if "winner" not in st:
                host = st.get("host_s")
                dev = st.get("device_s")
                st["winner"] = (
                    "host" if host is not None and (dev is None or host < dev)
                    else "device"
                )
                st["by"] = "measured"
                winner_new = True
            else:
                winner_new = False
            # the return value is captured under the lock too: the
            # post-release re-read raced record_device_failure's pin
            winner = st["winner"]
        if winner_new:
            self._count_winner(n_pad)
        return winner

    def _time_link(self, arrays: dict, n_rows: int) -> Optional[float]:
        try:
            import jax

            # untimed warmup: first transfer pays one-time backend init
            w = jax.device_put(np.zeros(16, dtype=np.int32))  # hslint: disable=HS019 - probe MEASURES the link; tracing probe bytes would pollute query traces
            w.block_until_ready()
            np.asarray(w)  # hslint: disable=HS019 - probe readback, not query data
            t0 = time.perf_counter()
            for a in arrays.values():
                d = jax.device_put(np.ascontiguousarray(a))
                d.block_until_ready()
            # readback floor: the mask comes home as one byte per row
            back = jax.device_put(np.zeros(n_rows, dtype=np.int8))
            back.block_until_ready()
            np.asarray(back)
            return time.perf_counter() - t0
        except Exception:  # noqa: BLE001 - probing must never fail a scan
            # a failed link probe gates every scan host-side with nothing
            # in the scan.gate.* metrics saying why — count it
            metrics.incr("scan.gate.probe_link_error")
            return None

    def _count_winner(self, n_pad: int) -> None:
        with self._lock:
            winner = self._state[n_pad]["winner"]
        metrics.incr(f"scan.gate.chose_{winner}")

    # -- observability -------------------------------------------------------
    def snapshot(self) -> dict:
        """Probe evidence per size class — recorded by the bench so the
        routing verdict ("why didn't the device fire?") is an artifact,
        not an assumption."""
        out = {}
        with self._lock:
            items = [(k, dict(v)) for k, v in sorted(self._state.items())]
        for n_pad, st in items:
            row = {}
            for k in ("host_s", "link_s", "device_s"):
                if k in st:
                    row[k] = round(st[k], 5)
            for k in ("winner", "by"):
                if k in st:
                    row[k] = st[k]
            out[str(n_pad)] = row
        return out

    def note_resident_bypass(self, kind: str) -> None:
        """Record a scan the gate never arbitrated because residency made
        the device the winner outright (module note "RESIDENCY-
        AWARENESS"). ``kind`` distinguishes the bypass families so the
        gate's metrics explain why no probe ladder ran: plain resident
        scans, the hybrid base+delta fused path
        ("scan.gate.resident_bypass_hybrid" under continuous appends is
        the delta fast path working, not a gate that went blind),
        resident joins ("scan.gate.resident_bypass_join" — the join
        region's codes are already on device, so the per-query H2D the
        gate's link arithmetic prices is zero by construction), and the
        oversubscribed tiers of the residency ladder ("…_compressed":
        packed planes already on device, same zero-H2D argument;
        "…_streaming": the window pipeline DOES re-pay H2D per query,
        but against the packed bytes with upload/compute overlapped —
        its admission ran at population time through the tier planner
        (residency.tiers), not through this gate's per-size probe, and
        the zone-fraction selectivity gate still applies upstream)."""
        metrics.incr(f"scan.gate.resident_bypass_{kind}")

    def reset(self) -> None:
        with self._lock:
            self._state.clear()
        with _heat_lock:
            _bucket_heat.clear()


# --- bucket heat (the background compactor's priority signal) ----------------
# Every runs-layout segment read notes which buckets a query touched,
# keyed by the index root the file set lives under; the incremental
# compactor (index/compactor.py) compacts the hottest buckets first so
# the queries actually running become join-competitive earliest. A plain
# bounded dict, not a metric: HS014 names are static, and the compactor
# needs the per-bucket ordering, not an aggregate.
_heat_lock = threading.Lock()
_bucket_heat: dict = {}  # index root -> {bucket: touch count}
_HEAT_ROOT_CAP = 64  # roots tracked; oldest-inserted evicted past this


def note_bucket_heat(root, buckets) -> None:
    """Count a query's touch of ``buckets`` under ``root`` (an index
    directory, or None — ignored). Called from the runs-layout read
    sites; cheap enough for the per-query path (one lock, k increments)."""
    if root is None:
        return
    root = str(root)
    with _heat_lock:
        per = _bucket_heat.get(root)
        if per is None:
            if len(_bucket_heat) >= _HEAT_ROOT_CAP:
                _bucket_heat.pop(next(iter(_bucket_heat)))
            per = _bucket_heat[root] = {}
        for b in buckets:
            b = int(b)
            per[b] = per.get(b, 0) + 1


def bucket_heat(root) -> dict:
    """A copy of the touch counts for ``root`` (empty when never seen)."""
    with _heat_lock:
        return dict(_bucket_heat.get(str(root), ()))


_atexit_registered = False


def _join_bg_threads_at_exit() -> None:
    """A daemon probe thread mid-device-transfer at interpreter shutdown
    races the jax runtime's teardown. Joining in-flight probes at exit
    keeps teardown clean."""
    global _atexit_registered
    if _atexit_registered:
        return
    _atexit_registered = True
    import atexit

    atexit.register(lambda: scan_gate.wait_probe(timeout=30.0))


scan_gate = ScanGate()
