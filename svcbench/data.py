"""Seeded host data generators for the benchmark's deployments.

TPC-H's tables, every column, by the generation rules of the TPC-H
specification (clause 4.2.3), with TPCD-Skew prices (Chaudhuri &
Narasayya): the skewed magnitudes of ``repro.data.synthetic.zipf_magnitudes``
(copied, so the yardstick stays fixed when the program's copy changes).
They return plain numpy column dicts: the harness uploads them into the
system's relations, and the reference reads the same arrays.

Encoding.  Keys are numbered from 0 (the specification's key k is k − 1
here) and ``o_orderkey`` is dense: the specification's sparse order keys
(8 of every 32) renumbered in order, which changes no join or grouping.
Dates are days since 1992-01-01 (int32), decimals float32, and every text
column an int32 code: the index of a value drawn from a list the
specification gives (ship modes, priorities, segments, ...), the key for a
name made from its key ("Customer#000000007"), and a draw from the seed
for free text (comments, addresses, phones).  Lineitem also carries
``l_linekey``, the row id its relation is keyed on.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

Columns = Dict[str, np.ndarray]

N_NATIONS = 25
N_REGIONS = 5
# the fixed n_regionkey of the 25 nations (clause 4.2.3, NATION)
NATION_REGION = np.array([0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0,
                          1, 2, 3, 4, 2, 3, 3, 1], np.int32)
START = np.datetime64("1992-01-01")
DAY = lambda d: int((np.datetime64(d) - START).astype(int))  # noqa: E731
END_DATE = DAY("1998-12-31")
CURRENT_DATE = DAY("1995-06-17")
# list sizes of clause 4.2.2.13 (types 6×5×5, containers 5×8, ...)
N_TYPES, N_CONTAINERS, N_SEGMENTS, N_PRIORITIES = 150, 40, 5, 5
N_INSTRUCT, N_MODES = 4, 7
FLAG_A, FLAG_N, FLAG_R, STATUS_F, STATUS_O, STATUS_P = (
    ord(c) for c in "ANRFOP")


def zipf_magnitudes(rng: np.random.Generator, n: int, z: float,
                    scale: float = 100.0) -> np.ndarray:
    """Long-tailed positive magnitudes: scale / rank^z of a random rank."""
    ranks = rng.integers(1, 10_000, size=n).astype(np.float64)
    vals = scale * 10_000.0 / np.power(ranks, z)
    return vals.astype(np.float32)


def _i32(rng, lo, hi, n):
    """n integers uniform on [lo, hi]."""
    return rng.integers(lo, hi + 1, n).astype(np.int32)


def _cents(rng, lo, hi, n):
    """n decimals uniform on [lo, hi] in steps of 0.01."""
    return (_i32(rng, round(lo * 100), round(hi * 100), n) / 100.0).astype(np.float32)


def _text(rng, n):
    """n int32 codes for free text."""
    return rng.integers(0, 2**31 - 1, n).astype(np.int32)


def suppkey(partkey: np.ndarray, i: np.ndarray, n_suppliers: int) -> np.ndarray:
    """The i-th supplier (0..3) of a part (PS_SUPPKEY / L_SUPPKEY rule),
    on keys numbered from 0."""
    S = n_suppliers
    p = partkey.astype(np.int64) + 1
    return ((p + i * (S // 4 + (p - 1) // S)) % S).astype(np.int32)


def dimensions(rng: np.random.Generator, n_customers: int, n_parts: int,
               n_suppliers: int, z: float) -> Dict[str, Columns]:
    """region, nation, supplier, customer, part and partsupp."""
    region = {"r_regionkey": np.arange(N_REGIONS, dtype=np.int32),
              "r_name": np.arange(N_REGIONS, dtype=np.int32),
              "r_comment": _text(rng, N_REGIONS)}
    nation = {"n_nationkey": np.arange(N_NATIONS, dtype=np.int32),
              "n_name": np.arange(N_NATIONS, dtype=np.int32),
              "n_regionkey": NATION_REGION.copy(),
              "n_comment": _text(rng, N_NATIONS)}
    S = n_suppliers
    supplier = {"s_suppkey": np.arange(S, dtype=np.int32),
                "s_name": np.arange(S, dtype=np.int32),
                "s_address": _text(rng, S),
                "s_nationkey": _i32(rng, 0, N_NATIONS - 1, S),
                "s_phone": _text(rng, S),
                "s_acctbal": _cents(rng, -999.99, 9999.99, S),
                "s_comment": _text(rng, S)}
    C = n_customers
    customer = {"c_custkey": np.arange(C, dtype=np.int32),
                "c_name": np.arange(C, dtype=np.int32),
                "c_address": _text(rng, C),
                "c_nationkey": _i32(rng, 0, N_NATIONS - 1, C),
                "c_phone": _text(rng, C),
                "c_acctbal": _cents(rng, -999.99, 9999.99, C),
                "c_mktsegment": _i32(rng, 0, N_SEGMENTS - 1, C),
                "c_comment": _text(rng, C)}
    P = n_parts
    part = {"p_partkey": np.arange(P, dtype=np.int32),
            "p_name": _text(rng, P),
            "p_mfgr": _i32(rng, 1, 5, P)}
    part["p_brand"] = (part["p_mfgr"] * 10 + _i32(rng, 1, 5, P)).astype(np.int32)
    part.update({"p_type": _i32(rng, 0, N_TYPES - 1, P),
                 "p_size": _i32(rng, 1, 50, P),
                 "p_container": _i32(rng, 0, N_CONTAINERS - 1, P),
                 # TPCD-Skew: long-tailed prices in place of the formula
                 "p_retailprice": zipf_magnitudes(rng, P, z),
                 "p_comment": _text(rng, P)})
    ps_part = np.repeat(np.arange(P, dtype=np.int32), 4)
    partsupp = {"ps_partkey": ps_part,
                "ps_suppkey": suppkey(ps_part, np.tile(np.arange(4), P), S),
                "ps_availqty": _i32(rng, 1, 9999, 4 * P),
                "ps_supplycost": _cents(rng, 1.00, 1000.00, 4 * P),
                "ps_comment": _text(rng, 4 * P)}
    # partsupp is keyed on (ps_partkey, ps_suppkey); a part's 4 suppliers
    # are distinct, so one int32 row id stands for the pair
    partsupp["ps_key"] = np.arange(4 * P, dtype=np.int32)
    return {"region": region, "nation": nation, "supplier": supplier,
            "customer": customer, "part": part, "partsupp": partsupp}


def orders_lineitems(rng: np.random.Generator, first_order: int, n_orders: int,
                     first_line: int, n_customers: int, part: Columns,
                     n_suppliers: int) -> Tuple[Columns, Columns]:
    """n_orders orders with keys from ``first_order`` and their 1–7
    lineitems each, with row ids from ``first_line`` (ORDERS and LINEITEM
    rules; RF1's new orders are made by the same rules)."""
    O = n_orders
    okey = np.arange(first_order, first_order + O, dtype=np.int32)
    # customers whose key (from 1) is divisible by 3 place no orders
    cust = rng.integers(0, (2 * n_customers) // 3, O)
    custkey = (3 * (cust // 2) + cust % 2).astype(np.int32)
    odate = _i32(rng, 0, END_DATE - 151, O)
    per = _i32(rng, 1, 7, O)
    L = int(per.sum())
    owner = np.repeat(np.arange(O), per)
    starts = np.cumsum(per) - per
    linenumber = (np.arange(L) - np.repeat(starts, per) + 1).astype(np.int32)
    partkey = _i32(rng, 0, part["p_partkey"].shape[0] - 1, L)
    qty = _i32(rng, 1, 50, L).astype(np.float32)
    price = (qty * part["p_retailprice"][partkey]).astype(np.float32)
    disc = _cents(rng, 0.0, 0.10, L)
    tax = _cents(rng, 0.0, 0.08, L)
    ship = (odate[owner] + _i32(rng, 1, 121, L)).astype(np.int32)
    commit = (odate[owner] + _i32(rng, 30, 90, L)).astype(np.int32)
    receipt = (ship + _i32(rng, 1, 30, L)).astype(np.int32)
    returned = np.where(rng.random(L) < 0.5, FLAG_R, FLAG_A)
    lineitem = {
        "l_linekey": np.arange(first_line, first_line + L, dtype=np.int32),
        "l_orderkey": okey[owner],
        "l_partkey": partkey,
        "l_suppkey": suppkey(partkey, rng.integers(0, 4, L), n_suppliers),
        "l_linenumber": linenumber,
        "l_quantity": qty,
        "l_extendedprice": price,
        "l_discount": disc,
        "l_tax": tax,
        "l_returnflag": np.where(receipt <= CURRENT_DATE, returned,
                                 FLAG_N).astype(np.int32),
        "l_linestatus": np.where(ship > CURRENT_DATE, STATUS_O,
                                 STATUS_F).astype(np.int32),
        "l_shipdate": ship,
        "l_commitdate": commit,
        "l_receiptdate": receipt,
        "l_shipinstruct": _i32(rng, 0, N_INSTRUCT - 1, L),
        "l_shipmode": _i32(rng, 0, N_MODES - 1, L),
        "l_comment": _text(rng, L),
    }
    charge = price.astype(np.float64) * (1.0 + tax) * (1.0 - disc)
    n_open = np.bincount(owner, weights=lineitem["l_linestatus"] == STATUS_O,
                         minlength=O)
    status = np.where(n_open == per, STATUS_O,
                      np.where(n_open == 0, STATUS_F, STATUS_P))
    orders = {
        "o_orderkey": okey,
        "o_custkey": custkey,
        "o_orderstatus": status.astype(np.int32),
        "o_totalprice": np.bincount(owner, weights=charge,
                                    minlength=O).astype(np.float32),
        "o_orderdate": odate,
        "o_orderpriority": _i32(rng, 0, N_PRIORITIES - 1, O),
        "o_clerk": _i32(rng, 0, max(1, n_customers // 150) - 1, O),
        "o_shippriority": np.zeros(O, np.int32),
        "o_comment": _text(rng, O),
    }
    return orders, lineitem
