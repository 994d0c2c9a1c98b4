"""TPC-H `lineitem`, the columns the group-by queries read, generated on
the device with dbgen's laws (TPC-H specification 4.2.3):

- lines per order uniform 1..7, rows clustered by order as dbgen writes them;
- o_orderdate uniform in [1992-01-01, 1998-12-31 - 151 days];
- l_quantity uniform 1..50;
- l_shipdate = o_orderdate + 1..121 days, l_receiptdate = l_shipdate + 1..30;
- l_returnflag R or A (even odds) when l_receiptdate <= 1995-06-17, else N;
- l_linestatus O when l_shipdate > 1995-06-17, else F.

Order keys are dense (0 .. orders-1).  The row count is pinned to
cfg["rows"] for every seed: the lines past it, at the end of the last
orders, are dropped, and a draw whose total falls short is redrawn from the
next stream of the key.  Flags and statuses are coded as small ids:
returnflag A=0 N=1 R=2, linestatus F=0 O=1.
"""
from __future__ import annotations

import datetime
from functools import partial

import jax
import jax.numpy as jnp

EPOCH = datetime.date(1992, 1, 1)
CURRENT_DAY = (datetime.date(1995, 6, 17) - EPOCH).days          # 1263
LAST_ORDER_DAY = (datetime.date(1998, 12, 31) - EPOCH).days - 151  # 2405
FLAGS = ("A", "N", "R")
STATUSES = ("F", "O")
REDRAWS = 16


@partial(jax.jit, static_argnames=("orders",))
def _lines_per_order(key, orders: int):
    counts = jax.random.randint(key, (orders,), 1, 8, jnp.int32)
    return counts, counts.sum()


@partial(jax.jit, static_argnames=("rows",))
def _rows(keys, counts, rows: int):
    starts = jnp.cumsum(counts) - counts
    mark = jnp.zeros(rows, jnp.int32).at[starts].add(1, mode="drop")
    orderkey = jnp.cumsum(mark) - 1
    odate = jax.random.randint(keys[0], counts.shape, 0, LAST_ORDER_DAY + 1)
    ship = odate[orderkey] + jax.random.randint(keys[1], (rows,), 1, 122)
    receipt = ship + jax.random.randint(keys[2], (rows,), 1, 31)
    r_or_a = jax.random.bernoulli(keys[3], 0.5, (rows,))
    flag = jnp.where(receipt <= CURRENT_DAY, jnp.where(r_or_a, 2, 0), 1)
    status = (ship > CURRENT_DAY).astype(jnp.int32)
    qty = jax.random.randint(keys[4], (rows,), 1, 51)
    f32 = jnp.float32
    return {"l_orderkey": orderkey.astype(f32), "l_quantity": qty.astype(f32),
            "l_returnflag": flag.astype(f32),
            "l_linestatus": status.astype(f32)}


def generate(cfg: dict, key) -> tuple[dict, dict]:
    """(columns, domains) of one input set, on the default device."""
    orders, rows = cfg["orders"], cfg["rows"]
    k_counts, k_rows = jax.random.split(key)
    for attempt in range(REDRAWS):
        counts, total = _lines_per_order(jax.random.fold_in(k_counts, attempt),
                                         orders)
        if int(total) >= rows:
            break
    else:
        raise RuntimeError(f"{REDRAWS} draws of {orders} orders all fell "
                           f"short of {rows} rows")
    cols = _rows(jax.random.split(k_rows, 5), counts, rows)
    return cols, {"l_orderkey": orders, "l_returnflag": len(FLAGS),
                  "l_linestatus": len(STATUSES), "l_quantity": None}
