"""reliability/: crash-consistent lifecycle machinery.

Four pieces, layered over the FileSystem seam and the Action protocol:

* ``retry``    — classified storage errors, bounded-backoff RetryPolicy
                 with deterministic jitter, RetryingFileSystem decorator;
* ``lease``    — heartbeated writer leases with epoch fencing next to
                 the operation log;
* ``recovery`` — automatic rollback of abandoned writers (transient log
                 head + expired lease) and crash-litter sweeping;
* ``doctor``   — fsck over index directories (log-chain integrity, data
                 presence, orphan reporting/vacuum);
* ``faults``   — deterministic fault injection for the chaos harness;
* ``chaos``    — the same replayable-schedule discipline one tier up:
                 scheduled host crash / stall / flap / slow faults at
                 the serve boundary (a FaultPlan).

See docs/12-reliability.md for the protocol walk-through.
"""

from .chaos import ChaosHostProxy, FaultPlan, HostFault
from .doctor import DoctorReport, Issue, doctor
from .faults import FaultInjectingFileSystem, FaultRule, InjectedCrash, crash_at
from .lease import DEFAULT_LEASE_DURATION_S, HeldLease, LeaseManager, LeaseRecord
from .recovery import (
    maybe_auto_recover,
    recover_abandoned_indexes,
    sweep_orphan_tmp_files,
)
from .retry import (
    DEFAULT_RETRY_POLICY,
    RetryingFileSystem,
    RetryPolicy,
    call_with_retries,
    classify_error,
    wrap_with_retries,
)

__all__ = [
    "ChaosHostProxy",
    "DEFAULT_LEASE_DURATION_S",
    "DEFAULT_RETRY_POLICY",
    "DoctorReport",
    "FaultInjectingFileSystem",
    "FaultPlan",
    "FaultRule",
    "HostFault",
    "HeldLease",
    "InjectedCrash",
    "Issue",
    "LeaseManager",
    "LeaseRecord",
    "RetryPolicy",
    "RetryingFileSystem",
    "call_with_retries",
    "classify_error",
    "crash_at",
    "doctor",
    "maybe_auto_recover",
    "recover_abandoned_indexes",
    "sweep_orphan_tmp_files",
    "wrap_with_retries",
]
