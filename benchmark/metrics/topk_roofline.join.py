"""Share of the HBM roofline of the filtered join-aggregate top-k
program (``jit_mesh_join_topk``): the least time the window's completed
queries need at each chip's peak HBM bandwidth, over the program's
device time summed over the chips' planes. The least bytes of a query
are Q3's columns, read once, from the source schema alone: every
lineitem row's l_orderkey, l_extendedprice and l_discount (8 bytes each)
and l_shipdate (4); every orders row's o_orderkey and o_custkey (8
each), o_orderdate and o_shippriority (4 each); and every customer
row's c_custkey (8) and c_mktsegment (CHAR(10)) on each chip, where the
broadcast side is read. Both sides are summed over the chips, as in
``sharded_roofline.mesh``."""

import re

from benchmark.common import peaks

PROGRAM = re.compile(r"^jit_mesh_join_topk")
LINEITEM_BYTES = 3 * 8 + 4
ORDERS_BYTES = 2 * 8 + 2 * 4
CUSTOMER_BYTES = 8 + 10


def least_bytes(rows: dict, chips: int) -> int:
    """The least bytes one query reads, over all chips."""
    return (rows["lineitem"] * LINEITEM_BYTES + rows["orders"] * ORDERS_BYTES
            + chips * rows["customer"] * CUSTOMER_BYTES)


def read(run):
    p = run.profile
    done = sum(r.ok for r in run.records)
    if not p or not done or not {"lineitem", "orders", "customer"} <= set(run.rows):
        return None
    device_s = sum(s for name, s in p["module_s"].items() if PROGRAM.search(name))
    if device_s <= 0:
        return None
    least = done * least_bytes(run.rows, run.cell["chips"])
    return 100.0 * least / peaks.peak(run.device_kind, "hbm_bytes_per_s") / device_s
