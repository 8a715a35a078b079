"""dbgen's rules that SSB and TPC-H share (both descend from TPC-H's dbgen)."""

from __future__ import annotations

import numpy as np


def order_lines(rng: np.random.Generator, n: int) -> tuple[np.ndarray, np.ndarray, int]:
    """`n` consecutive rows as orders of 1..7 lines, the last order cut where
    the rows end: (order index of each row, its 0-based line number, orders)."""
    lines = rng.integers(1, 8, max(n // 3, 8))
    ends = np.cumsum(lines)
    while ends[-1] < n:
        lines = np.concatenate([lines, rng.integers(1, 8, len(lines))])
        ends = np.cumsum(lines)
    n_orders = int(np.searchsorted(ends, n, "left")) + 1
    lines = lines[:n_orders].copy()
    lines[-1] -= ends[n_orders - 1] - n
    order = np.repeat(np.arange(n_orders), lines)
    starts = np.concatenate([[0], np.cumsum(lines)[:-1]])
    return order, (np.arange(n) - starts[order]).astype(np.int32), n_orders


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """p_retailprice of a 1-based part key, in cents (TPC-H 4.2.3)."""
    return 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1000)
