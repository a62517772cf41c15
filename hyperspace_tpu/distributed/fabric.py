"""Multi-host control plane: the fabric a pod-scale job stands on.

Before this subsystem every multi-host piece was hand-wired: the
two-process build worker called ``jax.distributed.initialize`` itself,
built its own global mesh, and owned its own bucket→process reasoning.
``QueryFabric`` is the one front door: it brings up the DCN control
plane (parallel.mesh.initialize_multihost — idempotent), constructs the
global 1-D bucket mesh over ALL devices in the job, exposes this
process's place in it, and answers placement questions — which DEVICE
owns a bucket (the shared ``owner_of_bucket`` rule) and therefore which
PROCESS owns it, which is exactly what a multi-host builder needs to
know to write only its own buckets, and what the router's partition map
expresses one level up at host granularity.

Single-process jobs connect trivially (the control plane no-ops, the
mesh covers local devices) — that's the tier-1 smoke-test configuration;
the two-process configuration is exercised by tests/test_multihost.py
through this same class.
"""

from __future__ import annotations

from typing import List, Optional

from ..exceptions import HyperspaceException
from ..ops import ensure_x64
from ..parallel.mesh import (
    BUCKET_AXIS,
    initialize_multihost,
    owner_of_bucket,
    process_info,
)
from ..telemetry.metrics import metrics

ensure_x64()

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

__all__ = ["QueryFabric"]


class QueryFabric:
    """One process's handle on the pod-wide execution fabric."""

    def __init__(
        self,
        coordinator_address: Optional[str] = None,
        num_processes: Optional[int] = None,
        process_id: Optional[int] = None,
        axis: str = BUCKET_AXIS,
    ):
        self.coordinator_address = coordinator_address
        self.num_processes = num_processes
        self.process_id = process_id
        self.axis = axis
        self._mesh: Optional[Mesh] = None

    # -- lifecycle ------------------------------------------------------------
    def connect(self) -> "QueryFabric":
        """Join the job: bring up the DCN control plane (no-op when
        single-process or already initialized) and build the global
        bucket mesh over every device in the job."""
        if self.coordinator_address is not None:
            initialize_multihost(
                coordinator_address=self.coordinator_address,
                num_processes=self.num_processes,
                process_id=self.process_id,
            )
        self._mesh = Mesh(np.array(jax.devices()), (self.axis,))
        metrics.incr("mesh.fabric.connected")
        return self

    @property
    def connected(self) -> bool:
        return self._mesh is not None

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            raise HyperspaceException("Fabric not connected; call connect().")
        return self._mesh

    # -- placement ------------------------------------------------------------
    def info(self) -> dict:
        return process_info()

    def owner_device_of_bucket(self, bucket: int):
        """The device a bucket lives on, via the ONE shared rule."""
        flat = self.mesh.devices.reshape(-1)
        return flat[owner_of_bucket(bucket, flat.size)]

    def owner_process_of_bucket(self, bucket: int) -> int:
        return self.owner_device_of_bucket(bucket).process_index

    def local_buckets(self, num_buckets: int) -> List[int]:
        """Buckets owned by THIS process's devices — the set a multi-host
        builder is responsible for writing."""
        me = jax.process_index()
        return [
            b
            for b in range(num_buckets)
            if self.owner_process_of_bucket(b) == me
        ]

    # -- serving --------------------------------------------------------------
    def make_router(
        self,
        sessions,
        serve_config=None,
        health_policy=None,
        retry_policy=None,
        hedging: bool = True,
    ):
        """Stand the serve front up over ``{host: session}``: one
        QueryServer per host session plus the health-directed
        QueryRouter fronting them — the one assembly path every
        multi-host serving test and a real pod share,
        so the failure-domain wiring (health director, hedges, retry
        budgets) is never re-plumbed by hand."""
        from ..serve.server import QueryServer, ServeConfig
        from .router import QueryRouter

        if not sessions:
            raise HyperspaceException("make_router needs at least one session.")
        servers = {
            name: QueryServer(sess, serve_config or ServeConfig())
            for name, sess in sessions.items()
        }
        return QueryRouter(
            servers,
            health_policy=health_policy,
            retry_policy=retry_policy,
            hedging=hedging,
        )

    # -- build ---------------------------------------------------------------
    def build_sharded(self, batch, key_names, num_buckets, scratch_dir=None):
        """The multi-controller sharded build, on the fabric's mesh: each
        process feeds its local rows, every process returns its local
        devices' bucket slices plus the replicated global counts
        (ops.build.build_partition_sharded_multihost)."""
        from ..ops.build import build_partition_sharded_multihost

        return build_partition_sharded_multihost(
            batch, key_names, num_buckets, self.mesh, scratch_dir=scratch_dir
        )
