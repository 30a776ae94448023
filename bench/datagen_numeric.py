"""Seeded numeric LINEITEM columns for the Q1/Q6 deployment (numpy only).

``lineitem_numeric`` draws the rows with ``datagen.lineitem_rows`` (TPC-H
v3 §4.2.3: orders of 1..7 lines, ship, receipt and return rules,
``l_quantity`` 1..50, ``l_discount`` 0..10 hundredths) and adds what Q1
and Q6 read beyond them: ``l_partkey`` uniform over SF 1's 200,000 parts
and ``l_extendedprice = l_quantity × p_retailprice`` in cents, with
§4.2.3's ``P_RETAILPRICE = (90000 + ((P_PARTKEY/10) mod 20001)
+ 100·(P_PARTKEY mod 1000))/100``. ``l_shipdate`` is the day number
since 1992-01-01. Nothing here imports the system under test.
"""
from __future__ import annotations

import datetime

import numpy as np

import datagen

PARTS_PER_SF = 200_000
BASE_COLUMNS = ("l_returnflag", "l_quantity", "l_discount", "l_shipdate")


def day_number(day: datetime.date) -> int:
    """Days since TPC-H's STARTDATE, 1992-01-01."""
    return (day - datagen.START_DATE).days


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """§4.2.3's P_RETAILPRICE of each part, in cents."""
    pk = np.asarray(partkey, np.int64)
    return 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)


def lineitem_numeric(n_rows: int, seed: int, *,
                     scale_factor: int = 1) -> dict:
    """``n_rows`` rows as columns: ``l_returnflag`` (str) and the int64
    columns ``l_quantity``, ``l_extendedprice`` (cents), ``l_discount``
    (hundredths), ``l_shipdate`` (days since 1992-01-01), ``l_partkey``."""
    rows = datagen.lineitem_rows(n_rows, seed, BASE_COLUMNS,
                                 scale_factor=scale_factor)
    flag, qty, disc, ship = zip(*rows)
    rng = np.random.default_rng(datagen.sub_seeds(seed, 4)[3])
    partkey = rng.integers(1, PARTS_PER_SF * scale_factor + 1, n_rows)
    quantity = np.asarray(qty, np.int64)
    return {
        "l_returnflag": np.asarray(flag),
        "l_quantity": quantity,
        "l_extendedprice": quantity * retail_price_cents(partkey),
        "l_discount": np.asarray(disc, np.int64),
        "l_shipdate": np.asarray(
            [day_number(datetime.date.fromisoformat(d)) for d in ship],
            np.int64),
        "l_partkey": partkey,
    }
