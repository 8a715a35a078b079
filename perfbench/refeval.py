"""The plain reference: filter / group / aggregate over numpy arrays.

Imports nothing of the program. A dataset module describes each of its query
templates as a `Spec`; `partial` evaluates one spec on one generated segment
and returns a small mergeable dict, `finish` merges the segments' partials
into the rows the broker should answer with.

Columns arrive as `Column(codes, vocab)`: a dictionary-coded column carries
integer codes into a sorted vocabulary that is the same in every segment of
the table, a raw column has `vocab=None` and its values in `codes`. Grouping
works on the codes, so partials of different segments share their keys.
"""

from __future__ import annotations

import operator
from dataclasses import dataclass, field
from typing import Callable, NamedTuple

import numpy as np


class Column(NamedTuple):
    codes: np.ndarray
    vocab: np.ndarray | None = None

    def values(self) -> np.ndarray:
        return self.codes if self.vocab is None else self.vocab[self.codes]


def code_of(col: Column, value) -> int:
    """The code of `value` in a coded column's vocabulary; -1 if absent."""
    i = int(np.searchsorted(col.vocab, value))
    return i if i < len(col.vocab) and col.vocab[i] == value else -1


def code_range(col: Column, lo, hi) -> tuple[int, int]:
    """Codes c with lo <= vocab[c] <= hi, as a half-open range."""
    return int(np.searchsorted(col.vocab, lo, "left")), int(np.searchsorted(col.vocab, hi, "right"))


@dataclass
class Spec:
    """One query template, for the reference.

    where(cols, params) -> bool mask; keys: group-by column names; aggs: list
    of (kind, value_fn) with kind in sum|count|avg|min|max and value_fn(cols)
    -> array (ignored for count); select: output order, key column names and "agg<i>";
    order: [(select name, descending)], the query's ORDER BY; exact: whether
    every aggregate is an integer the program must return exactly.
    """

    where: Callable
    keys: list[str] = field(default_factory=list)
    aggs: list[tuple[str, Callable | None]] = field(default_factory=list)
    select: list[str] = field(default_factory=list)
    order: list[tuple[str, bool]] = field(default_factory=list)
    exact: bool = True


@dataclass
class Template:
    """A query template: SQL with `{placeholders}`, the rule that draws their
    values from a numpy Generator, and the reference's spec of the same query."""

    sql: str
    draw: Callable  # (rng) -> params dict
    spec: Spec

    def render(self, params: dict) -> str:
        return self.sql.format(**params)


def partial(spec: Spec, params: dict, cols: dict[str, Column], acc_dtype=np.float64) -> dict:
    """Per-segment partial: {"n": matched rows, "kinds": each aggregate's kind,
    "groups": {key codes: [an aggregate's sum or extreme, ..., matched rows of the group]}}.

    Sums accumulate in float64, which is exact for the integer columns here
    (every partial sum stays far below 2**53). `acc_dtype=np.float32` is the
    lower-precision control, never the reference; it changes how sums and
    averages are added up and leaves `min` and `max`, which add nothing, alone.
    A group's extreme over no rows (a query without GROUP BY that matched
    nothing) is the identity of its kind, +inf for `min` and -inf for `max`.
    """
    mask = spec.where(cols, params)
    idx = np.flatnonzero(mask)
    n = len(idx)
    if spec.keys:
        gid = np.zeros(n, dtype=np.int64)
        for k in spec.keys:
            c = cols[k]  # a group key is a coded column: its vocabulary is the table's
            gid = gid * len(c.vocab) + c.codes[idx]
        uniq, inv = np.unique(gid, return_inverse=True)
    else:
        uniq, inv = np.zeros(1, dtype=np.int64), np.zeros(n, dtype=np.int64)
    counts = np.bincount(inv, minlength=len(uniq))
    sums = []
    for kind, fn in spec.aggs:
        if kind == "count":
            sums.append(counts.astype(np.float64))
        elif kind in _EXTREMES:
            ufunc, identity = _EXTREMES[kind]
            extreme = np.full(len(uniq), identity)
            ufunc.at(extreme, inv, np.asarray(fn(cols))[idx].astype(np.float64))
            sums.append(extreme)
        elif acc_dtype is np.float32:
            sums.append(_sum_float32(np.asarray(fn(cols))[idx], inv, counts))
        else:
            vals = np.asarray(fn(cols))[idx].astype(np.float64)
            sums.append(np.bincount(inv, weights=vals, minlength=len(uniq)))
    groups = {
        int(g): [float(s[i]) for s in sums] + [int(counts[i])]
        for i, g in enumerate(uniq)
        if counts[i] or not spec.keys
    }
    return {"n": n, "kinds": [kind for kind, _ in spec.aggs], "groups": groups}


#: a kind that keeps a group's extreme: how two values combine, and what no value at all reads
_EXTREMES = {"min": (np.minimum, np.inf), "max": (np.maximum, -np.inf)}


def _sum_float32(vals: np.ndarray, inv: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Per-group sums with the values and the running sum held in float32."""
    if not len(vals):
        return np.zeros(len(counts))
    running = np.cumsum(vals[np.argsort(inv, kind="stable")].astype(np.float32), dtype=np.float32)
    ends = np.cumsum(counts)
    at_end = running[np.maximum(ends - 1, 0)].astype(np.float64)
    return np.diff(np.concatenate([[0.0], at_end]))


def merge(partials: list[dict]) -> dict:
    """The segments' partials as one: a group's sums and counts add up, of its
    extremes the smaller or the larger stands, each field by its aggregate's kind."""
    kinds = partials[0]["kinds"] if partials else []
    combine = [min if k == "min" else max if k == "max" else operator.add for k in kinds] + [operator.add]  # the last field: the group's rows
    out: dict[int, list] = {}
    n = 0
    for p in partials:
        n += p["n"]
        for g, vals in p["groups"].items():
            cur = out.get(g)
            out[g] = vals if cur is None else [f(a, b) for f, a, b in zip(combine, cur, vals)]
    return {"n": n, "kinds": kinds, "groups": out}


def finish(spec: Spec, merged: dict, vocabs: dict[str, np.ndarray]) -> list[list]:
    """Rows in the spec's select order; key codes decode through `vocabs`.
    A query without GROUP BY answers one row even over no rows."""
    rows = []
    for g, vals in merged["groups"].items():
        *sums, count = vals
        if spec.keys and count == 0:
            continue
        key_vals = {}
        for k in reversed(spec.keys):
            g, code = divmod(g, len(vocabs[k]))
            key_vals[k] = vocabs[k][code].item()
        aggs = []
        for (kind, _), s in zip(spec.aggs, sums):
            if kind == "avg":
                aggs.append(s / count if count else float("-inf"))
            else:
                aggs.append(s)
        row = []
        for name in spec.select:
            row.append(aggs[int(name[3:])] if name.startswith("agg") else key_vals[name])
        rows.append(row)
    return rows
