"""TPC-H tables, every column, shaped after dbgen, from a seed:
``lineitem`` and ``orders`` together, ``customer`` and ``part`` each on
its own seeded stream, so that listing them leaves the first two as
they are. ``tables`` makes the tables a configuration lists.

``tpch_tables`` and ``write_files`` started as copies of
``chip_smoke.py``'s (PR 21), so that a change to the program cannot
change the data the benchmark measures on; the columns chip_smoke left
out (flags, the commit and receipt dates, the CHAR columns and the
comments) are added here by dbgen's rules. ``rf1_sets`` is TPC-H v3
§2.27's refresh function RF1, with the new orders keyed in dbgen's unused
sparse keys.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

SF1_ORDERS = 1_500_000
SF1_LINEITEM = 6_001_215
SF1_PARTS = 200_000
SF1_CUSTOMERS = 150_000
# dbgen's order-date window: STARTDATE 1992-01-01 to ENDDATE - 151 days
START_DAY = int(np.datetime64("1992-01-01", "D").astype(np.int64))
END_DAY = int(np.datetime64("1998-08-02", "D").astype(np.int64))
CURRENT_DAY = int(np.datetime64("1995-06-17", "D").astype(np.int64))  # dbgen's CURRENTDATE

# dbgen's value lists (dists.dss) for the CHAR columns
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
# the words of dbgen's comment grammar (dists.dss: nouns, verbs,
# adjectives, adverbs, prepositions, auxiliaries, terminators)
WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses platelets asymptotes "
    "courts dolphins multipliers sauternes warthogs frets dinos attainments somas Tiresias' "
    "patterns forges braids hockey players frays warhorses dugouts notornis epitaphs pearls "
    "tithes waters orbits gifts sheaves depths sentiments decoys realms pains grouches escapades "
    "packages requests accounts deposits sleep wake are cajole haggle nag use boost affix detect "
    "integrate maintain nod was lose sublate solve thrash promise engage hinder print x-ray breach "
    "eat grow impress mold poach serve run dazzle snooze doze unwind kindle play hang believe doubt "
    "furious sly careful blithe quick fluffy slow quiet ruthless thin close dogged daring brave "
    "stealthy permanent enticing idle busy regular final ironic even bold silent sometimes always "
    "never furiously slyly carefully blithely quickly fluffily slowly quietly ruthlessly thinly "
    "closely doggedly daringly bravely stealthily permanently enticingly idly busily regularly "
    "finally ironically evenly boldly silently about above across after against along among around "
    "at atop before behind beneath beside besides between beyond by despite during except for from "
    "inside into near of on outside over past since through throughout to toward under until up "
    "upon without with within do may might shall will would can could should must . ; : ? ! --"
).split()
POOL_BYTES = 1 << 23


def text_pool(rng) -> np.ndarray:
    """dbgen's text pool (TPC-H v3 4.2.2.10), smaller: words of its
    grammar drawn uniformly, space-separated, as bytes."""
    words = np.array([w.encode() for w in WORDS], dtype=object)
    n = POOL_BYTES // 6
    pool = b" ".join(words[rng.integers(0, len(words), n)].tolist())
    return np.frombuffer(pool, np.uint8)


def _text(rng, pool: np.ndarray, n: int, avg: int):
    """dbgen's TEXT(avg), as a pyarrow string array: lengths uniform in
    [0.4 avg, 1.6 avg]; the values are consecutive slices of a stream
    made of 1 MiB stretches of the pool at random offsets (dbgen takes
    each value at a random offset of the pool)."""
    import pyarrow as pa

    lo, hi = int(avg * 0.4), int(avg * 1.6)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(rng.integers(lo, hi + 1, n), out=offsets[1:])
    total, step = int(offsets[-1]), 1 << 20
    starts = rng.integers(0, len(pool) - step, -(-total // step))
    data = np.concatenate([pool[a:a + step] for a in starts])[:total]
    return pa.Array.from_buffers(
        pa.string(), n, [None, pa.py_buffer(offsets.astype(np.int32)), pa.py_buffer(data)])


def _pick(rng, values, n: int, codes=None):
    """``values[codes]`` as a string array; ``codes`` uniform when not
    given."""
    import pyarrow as pa

    if codes is None:
        codes = rng.integers(0, len(values), n)
    return pa.array(values).take(pa.array(codes))


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE of each part key (TPC-H v3 4.2.3), which
    ``l_extendedprice`` multiplies by the quantity."""
    return (90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)) / 100.0


def _lines_for(rng, pool, counts, o_orderkey, o_orderdate, n_parts, n_supp):
    """lineitem's 16 columns for orders with ``counts`` lines each, by
    dbgen's rules (TPC-H v3 4.2.3), and the orders' total prices and
    statuses."""
    import pyarrow as pa

    n_lines = int(counts.sum())
    first = np.cumsum(counts) - counts
    l_orderkey = np.repeat(o_orderkey, counts)
    l_linenumber = np.arange(n_lines) - np.repeat(first, counts) + 1
    l_partkey = rng.integers(1, n_parts + 1, n_lines).astype(np.int64)
    # PART_SUPP_BRIDGE: one of the part's 4 suppliers
    i = rng.integers(0, 4, n_lines)
    l_suppkey = (l_partkey + i * (n_supp // 4 + (l_partkey - 1) // n_supp)) % n_supp + 1
    l_quantity = rng.integers(1, 51, n_lines).astype(np.float64)
    l_extendedprice = np.round(l_quantity * retail_price(l_partkey), 2)
    l_discount = rng.integers(0, 11, n_lines) / 100.0
    l_tax = rng.integers(0, 9, n_lines) / 100.0
    ordered = np.repeat(o_orderdate, counts)
    l_shipdate = (ordered + rng.integers(1, 122, n_lines)).astype(np.int32)
    l_commitdate = (ordered + rng.integers(30, 91, n_lines)).astype(np.int32)
    l_receiptdate = (l_shipdate + rng.integers(1, 31, n_lines)).astype(np.int32)
    # returned lines are R or A at random, the rest N; shipped lines F
    returned = np.where(l_receiptdate <= CURRENT_DAY, rng.integers(1, 3, n_lines), 0)
    open_ = l_shipdate > CURRENT_DAY
    charge = l_extendedprice * (1 + l_tax) * (1 - l_discount)
    o_totalprice = np.round(np.add.reduceat(charge, first), 2)
    n_open = np.add.reduceat(open_.astype(np.int64), first)
    status = np.where(n_open == counts, 0, np.where(n_open == 0, 1, 2))
    lines = {
        "l_orderkey": l_orderkey,
        "l_partkey": l_partkey,
        "l_suppkey": l_suppkey.astype(np.int64),
        "l_linenumber": l_linenumber.astype(np.int32),
        "l_quantity": l_quantity,
        "l_extendedprice": l_extendedprice,
        "l_discount": l_discount,
        "l_tax": l_tax,
        "l_returnflag": _pick(rng, ["N", "R", "A"], n_lines, returned),
        "l_linestatus": _pick(rng, ["F", "O"], n_lines, open_.astype(np.int64)),
        "l_shipdate": pa.array(l_shipdate, pa.date32()),
        "l_commitdate": pa.array(l_commitdate, pa.date32()),
        "l_receiptdate": pa.array(l_receiptdate, pa.date32()),
        "l_shipinstruct": _pick(rng, INSTRUCTIONS, n_lines),
        "l_shipmode": _pick(rng, MODES, n_lines),
        "l_comment": _text(rng, pool, n_lines, 27),
    }
    return lines, o_totalprice, _pick(rng, ["O", "F", "P"], len(counts), status)


def _orders(rng, pool, o_orderkey, o_custkey, o_orderstatus, o_totalprice, o_orderdate, n_clerks):
    """orders' 9 columns (TPC-H v3 4.2.3)."""
    import pyarrow as pa

    n = len(o_orderkey)
    clerks = [f"Clerk#{c:09d}" for c in range(1, n_clerks + 1)]
    return {
        "o_orderkey": o_orderkey,
        "o_custkey": o_custkey,
        "o_orderstatus": o_orderstatus,
        "o_totalprice": o_totalprice,
        "o_orderdate": pa.array(o_orderdate, pa.date32()),
        "o_orderpriority": _pick(rng, PRIORITIES, n),
        "o_clerk": _pick(rng, clerks, n),
        "o_shippriority": np.zeros(n, dtype=np.int32),
        "o_comment": _text(rng, pool, n, 49),
    }


def sizes(scale: float):
    n_orders = max(int(SF1_ORDERS * scale), 8)
    n_lines = max(int(round(SF1_LINEITEM * scale)), n_orders)
    n_parts = max(int(SF1_PARTS * scale), 10)
    n_cust = max(int(SF1_CUSTOMERS * scale), 3)
    return n_orders, n_lines, n_parts, n_cust


def _scaled(scale: float, per_sf: int) -> int:
    return max(int(per_sf * scale), 4)


def tpch_tables(scale: float, seed: int):
    """(lineitem, orders) as pyarrow tables with all of the spec's
    columns. Row counts are SF1's times ``scale``; keys, dates, prices,
    flags and comments follow dbgen: sparse order keys (8 of every 32),
    1-7 lines per order, ship dates 1-121 days after the order date,
    extended price = quantity x the part's retail price. DECIMAL columns
    are float64, the engine's decimal representation (8 bytes, as
    DECIMAL(15,2) is in parquet)."""
    import pyarrow as pa

    rng = np.random.default_rng(np.random.SeedSequence([seed, 0]))
    pool = text_pool(np.random.default_rng(np.random.SeedSequence([seed, 2])))
    n_orders, n_lines, n_parts, n_cust = sizes(scale)

    i = np.arange(n_orders, dtype=np.int64)
    o_orderkey = (i // 8) * 32 + (i % 8) + 1
    o_custkey = rng.integers(1, n_cust + 1, n_orders).astype(np.int64)
    o_custkey += (o_custkey % 3 == 0)  # dbgen skips every third customer
    o_orderdate = rng.integers(START_DAY, END_DAY + 1, n_orders).astype(np.int32)

    counts = rng.integers(1, 8, n_orders)
    diff = n_lines - int(counts.sum())
    while diff:
        room = np.flatnonzero(counts < 7) if diff > 0 else np.flatnonzero(counts > 1)
        pick = rng.choice(room, min(abs(diff), len(room)), replace=False)
        counts[pick] += 1 if diff > 0 else -1
        diff -= len(pick) if diff > 0 else -len(pick)

    lines, o_totalprice, o_orderstatus = _lines_for(
        rng, pool, counts, o_orderkey, o_orderdate, n_parts, _scaled(scale, 10_000))
    orders = _orders(rng, pool, o_orderkey, o_custkey, o_orderstatus, o_totalprice, o_orderdate,
                     _scaled(scale, 1_000))
    return pa.table(lines), pa.table(orders)


def rf1_sets(scale: float, seed: int, n_sets: int):
    """``n_sets`` TPC-H RF1 insert sets as [(lineitem, orders)] pyarrow
    tables. Each inserts SF x 1,500 orders with 1-7 lines each. The new
    order keys are dbgen's sparse keys with sequence bits 1 (mk_sparse:
    key = ((i >> 3) << 5) | (1 << 3) | (i & 7), plus one), which the base
    data (sequence 0) never uses; set s takes the indices after set s-1."""
    import pyarrow as pa

    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    pool = text_pool(np.random.default_rng(np.random.SeedSequence([seed, 3])))
    per_set = max(int(round(1_500 * scale)), 1)
    _, _, n_parts, n_cust = sizes(scale)
    out = []
    for s in range(n_sets):
        i = np.arange(s * per_set, (s + 1) * per_set, dtype=np.int64)
        o_orderkey = ((i >> 3) << 5) + (1 << 3) + (i & 7) + 1
        o_custkey = rng.integers(1, n_cust + 1, per_set).astype(np.int64)
        o_custkey += (o_custkey % 3 == 0)
        o_orderdate = rng.integers(START_DAY, END_DAY + 1, per_set).astype(np.int32)
        counts = rng.integers(1, 8, per_set)
        lines, o_totalprice, o_orderstatus = _lines_for(
            rng, pool, counts, o_orderkey, o_orderdate, n_parts, _scaled(scale, 10_000))
        orders = _orders(rng, pool, o_orderkey, o_custkey, o_orderstatus, o_totalprice,
                         o_orderdate, _scaled(scale, 1_000))
        out.append((pa.table(lines), pa.table(orders)))
    return out


# customer's and part's value lists (dists.dss; TPC-H v3 4.2.2.13, 4.2.3)
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush brown burlywood "
    "burnished chartreuse chiffon chocolate coral cornflower cornsilk cream cyan dark deep dim "
    "dodger drab firebrick floral forest frosted gainsboro ghost goldenrod green grey honeydew hot "
    "indian ivory khaki lace lavender lawn lemon light lime linen magenta maroon medium metallic "
    "midnight mint misty moccasin navajo navy olive orange orchid pale papaya peach peru pink plum "
    "powder puff purple red rose rosy royal saddle salmon sandy seashell sienna sky slate smoke "
    "snow spring steel tan thistle tomato turquoise violet wheat white yellow"
).split()
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM")]
# dbgen's v-string alphabet: 64 characters
ALNUM = np.frombuffer(b"0123456789abcdefghijklmnopqrstuvwxyz ABCDEFGHIJKLMNOPQRSTUVWXYZ,", np.uint8)


def _digits(values: np.ndarray, width: int = 0):
    """Each value's decimal digits as a string, zero-padded to ``width``."""
    import pyarrow as pa
    import pyarrow.compute as pc

    s = pa.array(values).cast(pa.string())
    return pc.utf8_lpad(s, width, "0") if width else s


def _vstring(rng, n: int, lo: int, hi: int):
    """dbgen's random v-string [lo, hi] (4.2.2.7): lengths uniform,
    characters drawn from its 64."""
    import pyarrow as pa

    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(rng.integers(lo, hi + 1, n), out=offsets[1:])
    data = ALNUM[rng.integers(0, len(ALNUM), int(offsets[-1]))]
    return pa.Array.from_buffers(
        pa.string(), n, [None, pa.py_buffer(offsets.astype(np.int32)), pa.py_buffer(data.tobytes())])


def _phone(rng, nation: np.ndarray):
    """dbgen's phone number (4.2.2.9): country code nationkey + 10, then
    three random local groups, as CC-LLL-LLL-LLLL."""
    import pyarrow.compute as pc

    n = len(nation)
    return pc.binary_join_element_wise(
        _digits(nation + 10), _digits(rng.integers(100, 1_000, n)), _digits(rng.integers(100, 1_000, n)),
        _digits(rng.integers(1_000, 10_000, n)), "-")


def _part_names(rng, n: int):
    """P_NAME: five distinct colors, in the order drawn, space-separated."""
    import pyarrow as pa
    import pyarrow.compute as pc

    picks = np.zeros((n, 5), np.int64)
    for k in range(5):
        # the v-th color of those not yet picked: step over each earlier
        # pick, smallest first
        v = rng.integers(0, len(COLORS) - k, n)
        for prev in np.sort(picks[:, :k], axis=1).T:
            v += v >= prev
        picks[:, k] = v
    colors = pa.array(COLORS)
    return pc.binary_join_element_wise(*[colors.take(pa.array(picks[:, k])) for k in range(5)], " ")


def customer(scale: float, seed: int):
    """customer's 8 columns (TPC-H v3 4.2.3): keys 1..SF x 150,000, the
    range ``o_custkey`` is drawn from."""
    import pyarrow as pa
    import pyarrow.compute as pc

    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    pool = text_pool(rng)
    n = sizes(scale)[3]
    key = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, n).astype(np.int64)
    return pa.table({
        "c_custkey": key,
        "c_name": pc.binary_join_element_wise("Customer#", _digits(key, 9), ""),
        "c_address": _vstring(rng, n, 10, 40),
        "c_nationkey": nation,
        "c_phone": _phone(rng, nation),
        "c_acctbal": rng.integers(-99_999, 1_000_000, n) / 100.0,
        "c_mktsegment": _pick(rng, SEGMENTS, n),
        "c_comment": _text(rng, pool, n, 73),
    })


def part(scale: float, seed: int):
    """part's 9 columns (TPC-H v3 4.2.3): keys 1..SF x 200,000, the range
    ``l_partkey`` is drawn from; P_RETAILPRICE by the formula that
    ``l_extendedprice`` multiplies by the quantity."""
    import pyarrow as pa

    rng = np.random.default_rng(np.random.SeedSequence([seed, 8]))
    pool = text_pool(rng)
    n = sizes(scale)[2]
    key = np.arange(1, n + 1, dtype=np.int64)
    names = _part_names(rng, n)
    mfgr = rng.integers(0, 5, n)
    brand = mfgr * 5 + rng.integers(0, 5, n)
    return pa.table({
        "p_partkey": key,
        "p_name": names,
        "p_mfgr": _pick(rng, [f"Manufacturer#{m}" for m in range(1, 6)], n, mfgr),
        "p_brand": _pick(rng, [f"Brand#{m}{b}" for m in range(1, 6) for b in range(1, 6)], n, brand),
        "p_type": _pick(rng, TYPES, n),
        "p_size": rng.integers(1, 51, n).astype(np.int32),
        "p_container": _pick(rng, CONTAINERS, n),
        "p_retailprice": retail_price(key),
        "p_comment": _text(rng, pool, n, 14),
    })


def _lineitem_orders(scale: float, seed: int) -> dict:
    li, od = tpch_tables(scale, seed)
    return {"lineitem": li, "orders": od}


# table -> what makes it, as {table: pyarrow table}, with any table made
# alongside it
MAKERS = {
    "lineitem": _lineitem_orders,
    "orders": _lineitem_orders,
    "customer": lambda scale, seed: {"customer": customer(scale, seed)},
    "part": lambda scale, seed: {"part": part(scale, seed)},
}


def tables(names, scale: float, seed: int) -> dict:
    """{table: pyarrow table} of the TPC-H tables ``names``, in that order."""
    made = {}
    for name in names:
        if name not in made:
            if name not in MAKERS:
                raise KeyError(f"no generator for TPC-H table {name!r} (have {sorted(MAKERS)})")
            made.update(MAKERS[name](scale, seed))
    return {name: made[name] for name in names}


def write_files(table, directory: Path, n_files: int, prefix: str = "part") -> list:
    """``table`` as ``n_files`` parquet files of equal row counts."""
    import pyarrow.parquet as pq

    directory.mkdir(parents=True, exist_ok=True)
    per = -(-table.num_rows // n_files)
    paths = []
    for k in range(n_files):
        p = directory / f"{prefix}-{k:03d}.parquet"
        pq.write_table(table.slice(k * per, per), p)
        paths.append(p)
    return paths
