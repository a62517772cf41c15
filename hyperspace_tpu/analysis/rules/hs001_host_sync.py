"""HS001 — host-device synchronization in hot paths.

"Query Processing on Tensor Computation Runtimes" and "Theseus" both name
host-device data movement as the dominant perf hazard of tensor-runtime
query engines; a stray ``.item()`` or ``np.asarray`` on a device array
inside the execution or planning layers serializes the device pipeline.
This rule bans the four readback idioms inside ``exec/``, ``ops/`` and
``plan/``, except in the allow-listed *boundary modules* whose whole job
is device↔host marshalling. A site outside those modules that is a
genuine boundary carries an inline suppression with its justification.

Heuristics (static analysis cannot type arrays):
  * any ``<expr>.item()`` call;
  * any ``<expr>.block_until_ready()`` call;
  * any call resolving to ``numpy.asarray`` (import aliases followed);
  * ``int(x)``/``float(x)``/``bool(x)`` where ``x`` is a subscript — the
    classic device-scalar readback ``int(arr[0])``. Plain names and call
    results are NOT flagged (too noisy: ``int(np.searchsorted(...))`` is
    host math).
"""

from __future__ import annotations

import ast
from typing import Iterator, Tuple

from ..core import ModuleContext, Rule, dotted_name

SCOPE = (
    "hyperspace_tpu/exec/",
    "hyperspace_tpu/ops/",
    "hyperspace_tpu/plan/",
)

# Modules whose purpose IS the device<->host boundary: kernels marshal
# arguments and read results back, the scan/distributed layers rematerialize
# masks and partials on host, the HBM/mesh caches fence residency, the
# scan gate measures the link itself, and floatbits/bitpack
# ARE the transport formats (host-side encode of the f64 ordered planes
# and of the bit-packed residency words).
BOUNDARY_MODULES = (
    "hyperspace_tpu/ops/__init__.py",
    "hyperspace_tpu/ops/build.py",
    "hyperspace_tpu/ops/kernels.py",
    "hyperspace_tpu/ops/floatbits.py",
    "hyperspace_tpu/ops/bitpack.py",
    "hyperspace_tpu/exec/scan.py",
    "hyperspace_tpu/exec/scan_gate.py",
    "hyperspace_tpu/exec/distributed.py",
    "hyperspace_tpu/exec/hbm_cache.py",
    "hyperspace_tpu/exec/mesh_cache.py",
)

_CAST_NAMES = {"int", "float", "bool"}


class HostSyncRule(Rule):
    code = "HS001"
    name = "host-sync-in-hot-path"
    description = (
        "host-device synchronization (.item()/block_until_ready/np.asarray/"
        "scalar cast of a subscript) inside exec/, ops/ or plan/ outside the "
        "allow-listed boundary modules"
    )

    def applies_to(self, posix_path: str) -> bool:
        if not any(s in posix_path for s in SCOPE):
            return False
        return not any(posix_path.endswith(m) for m in BOUNDARY_MODULES)

    def check(self, ctx: ModuleContext) -> Iterator[Tuple[int, int, str]]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Attribute):
                if func.attr == "item" and not node.args and not node.keywords:
                    yield (
                        node.lineno,
                        node.col_offset,
                        ".item() forces a device->host scalar readback in a "
                        "hot path; keep results on device or move this to a "
                        "boundary module",
                    )
                    continue
                if func.attr == "block_until_ready":
                    yield (
                        node.lineno,
                        node.col_offset,
                        "block_until_ready() stalls the device pipeline in a "
                        "hot path; fence at the boundary module instead",
                    )
                    continue
            resolved = dotted_name(func, ctx.aliases)
            if resolved == "numpy.asarray":
                yield (
                    node.lineno,
                    node.col_offset,
                    "np.asarray here may DMA a device array back to host in "
                    "a hot path; materialize at a boundary module (suppress "
                    "with justification if the operand is host-resident)",
                )
                continue
            if (
                isinstance(func, ast.Name)
                and func.id in _CAST_NAMES
                and len(node.args) == 1
                and not node.keywords
                and isinstance(node.args[0], ast.Subscript)
            ):
                yield (
                    node.lineno,
                    node.col_offset,
                    f"{func.id}(<subscript>) reads one element back to host "
                    "(device-scalar readback idiom); batch the readback at a "
                    "boundary module",
                )
