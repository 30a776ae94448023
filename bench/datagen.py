"""Seeded data for the benchmark's configurations (numpy only).

``lineitem_rows`` follows the TPC-H Standard Specification v3: the
LINEITEM schema of §1.4.1 and the value rules of §4.2.3, for the columns a
configuration file names. ``embed_table`` makes an embedding table on the
device from the seed. Nothing here imports the system under test: the
reference in ``reference.py`` reads the same cleartext data.
"""
from __future__ import annotations

import datetime

import numpy as np

# §4.2.3: STARTDATE 1992-01-01, CURRENTDATE 1995-06-17,
# ENDDATE 1998-12-31; O_ORDERDATE uniform in [STARTDATE, ENDDATE - 151].
START_DATE = datetime.date(1992, 1, 1)
CURRENT_DATE = datetime.date(1995, 6, 17)
END_DATE = datetime.date(1998, 12, 31)
ORDERDATE_SPAN = (END_DATE - START_DATE).days - 151
SHIP_MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
ORDERS_PER_SF = 1_500_000


def sub_seeds(seed: int, n: int) -> list:
    """``n`` independent 32-bit seeds from one run seed of any size."""
    return [int(s) for s in np.random.SeedSequence(int(seed)).generate_state(n)]


def sparse_orderkey(i: np.ndarray) -> np.ndarray:
    """dbgen's sparse O_ORDERKEY for order number ``i`` (from 1): of every
    32 keys only the first 8 are used (§4.2.3, "O_ORDERKEY ... sparse")."""
    i = np.asarray(i, np.int64)
    return ((i >> 3) << 5) + (i & 7)


def lineitem_rows(n_rows: int, seed: int, columns, *,
                  scale_factor: int = 1) -> list:
    """``n_rows`` LINEITEM rows as strings, in ``columns`` order.

    Orders are drawn without replacement from the scale factor's order
    numbers and listed by key; each carries 1 to 7 lines, uniform (the last
    order is cut where the row count ends). Dates are ``YYYY-MM-DD``;
    ``l_quantity`` is 1..50; ``l_discount`` is in hundredths, 0..10.
    """
    rng = np.random.default_rng(sub_seeds(seed, 1)[0])
    n_orders = n_rows                      # enough: each order has >= 1 line
    order_no = np.sort(rng.choice(ORDERS_PER_SF * scale_factor, n_orders,
                                  replace=False) + 1)
    lines = rng.integers(1, 8, n_orders)
    per_order = np.repeat(np.arange(n_orders), lines)[:n_rows]
    orderkey = sparse_orderkey(order_no)[per_order]
    orderdate = rng.integers(0, ORDERDATE_SPAN + 1, n_orders)[per_order]
    shipdate = orderdate + rng.integers(1, 122, n_rows)
    receiptdate = shipdate + rng.integers(1, 31, n_rows)
    current = (CURRENT_DATE - START_DATE).days
    returnflag = np.where(receiptdate <= current,
                          np.where(rng.integers(0, 2, n_rows) == 0, "R", "A"),
                          "N")
    linestatus = np.where(shipdate > current, "O", "F")
    values = {
        "l_orderkey": [str(int(k)) for k in orderkey],
        "l_returnflag": list(returnflag),
        "l_linestatus": list(linestatus),
        "l_shipmode": [SHIP_MODES[i]
                       for i in rng.integers(0, len(SHIP_MODES), n_rows)],
        "l_shipdate": [(START_DATE + datetime.timedelta(days=int(d)))
                       .isoformat() for d in shipdate],
        "l_quantity": [str(int(q)) for q in rng.integers(1, 51, n_rows)],
        "l_discount": [str(int(d)) for d in rng.integers(0, 11, n_rows)],
    }
    return [list(r) for r in zip(*(values[c] for c in columns))]


def embed_table(key, vocab: int, width: int, clip: float):
    """A float32 (vocab, width) table, N(0, 1) clipped to ±``clip``, from a
    JAX key. Traceable: callers jit it, alone or inside the program that
    shares it, so the table is made on the device."""
    import jax
    import jax.numpy as jnp
    return jnp.clip(jax.random.normal(key, (vocab, width), jnp.float32),
                    -clip, clip)
