"""The comparison that decides `correct`.

Every response of the window is checked for shape (no exception, every
server answered, every row of the table was visible, no more rows than the
LIMIT). A sample drawn from the seed — some of every template, and the
slowest query of the window — is compared in full, every row and every
number, with the plain reference's answer over the same generated data.
Each number compared is printed beside its limit.
"""

from __future__ import annotations

import math

import numpy as np

from perfbench import refeval


def reference_partials(dataset: str, seed: int, index: int, rows: int, config: dict,
                       wanted: list[tuple[str, dict]], control: bool = False) -> list[dict]:  # fmt: skip
    """Worker: regenerate segment `index` and evaluate every sampled query on
    it. `control=True` sums in float32: the lower-precision control, which the
    comparison has to reject (perfbench/tests/test_control.py)."""
    import importlib

    ds = importlib.import_module(f"perfbench.datasets.{dataset}")
    cols = ds.segment(seed, index, rows, config)
    acc = np.float32 if control else np.float64
    return [refeval.partial(ds.TEMPLATES[name].spec, params, cols, acc) for name, params in wanted]


def pick_sample(samples: list, per_template: int, rng: np.random.Generator) -> list:
    """Of the queries that answered: `per_template` of each template, drawn
    from the seed, and the slowest of all."""
    ok = [s for s in samples if s.error is None]
    chosen: dict[int, object] = {}
    by_template: dict[str, list] = {}
    for s in ok:
        by_template.setdefault(s.template, []).append(s)
    for name in sorted(by_template):
        group = by_template[name]
        for i in rng.choice(len(group), min(per_template, len(group)), replace=False):
            chosen[group[i].index] = group[i]
    if ok:
        slowest = max(ok, key=lambda s: s.latency_ms)
        chosen[slowest.index] = slowest
    return [chosen[i] for i in sorted(chosen)]


def shape_error(sample, n_servers: int, total_rows: int, limit: int) -> str | None:
    doc = sample.doc
    # a query whose every segment the broker pruned by value asks no server, and says so by silence
    queried, responded = doc.get("numServersQueried", 0), doc.get("numServersResponded", 0)
    if responded != queried or queried not in (0, n_servers) or doc.get("partialResult"):
        return f"servers queried {queried}, responded {responded}, of {n_servers}; partial={doc.get('partialResult')}"
    if doc.get("totalDocs") != total_rows:
        return f"totalDocs {doc.get('totalDocs')} != {total_rows}"
    rows = doc.get("resultTable", {}).get("rows")
    if not isinstance(rows, list) or len(rows) > limit:
        return f"{None if rows is None else len(rows)} rows against LIMIT {limit}"
    return None


def compare_rows(spec: refeval.Spec, got: list[list], want: list[list]) -> dict:
    """The numbers of one compared answer: rows missing or extra (by group
    key), ORDER BY violations, the largest absolute difference of an exact
    aggregate and the largest relative error of an inexact one."""
    key_pos = [i for i, n in enumerate(spec.select) if not n.startswith("agg")]
    agg_pos = [i for i, n in enumerate(spec.select) if n.startswith("agg")]
    out = {"rows_missing_or_extra": 0, "order_violations": 0, "max_abs_diff": 0.0, "max_rel_err": 0.0}
    bad_width = [r for r in got if len(r) != len(spec.select)]
    if bad_width:
        out["rows_missing_or_extra"] = len(got) + len(want)
        return out
    g = {tuple(r[i] for i in key_pos): r for r in got}
    w = {tuple(r[i] for i in key_pos): r for r in want}
    out["rows_missing_or_extra"] = len(set(g) ^ set(w)) + (len(got) - len(g))
    for k in set(g) & set(w):
        for i in agg_pos:
            a, b = g[k][i], w[k][i]
            if not isinstance(a, (int, float)) or isinstance(a, bool) or not math.isfinite(a):
                out["rows_missing_or_extra"] += 1
                continue
            diff = abs(float(a) - float(b))
            out["max_abs_diff"] = max(out["max_abs_diff"], diff)
            out["max_rel_err"] = max(out["max_rel_err"], diff / max(abs(float(b)), 1e-300))
    pos = {n: i for i, n in enumerate(spec.select)}
    for prev, cur in zip(got, got[1:]):
        for name, desc in spec.order:
            a, b = prev[pos[name]], cur[pos[name]]
            if a == b:
                continue
            if (a < b) == desc:
                out["order_violations"] += 1
            break
    return out


def judge(numbers: dict, exact: bool, rel_tol: float) -> tuple[bool, list[str]]:
    """Each number beside its limit. An exact template's aggregates are
    integers: limit 0. An inexact one's are DOUBLE sums: the configuration's
    relative tolerance."""
    limits = {
        "rows_missing_or_extra": 0, "order_violations": 0,
        **({"max_abs_diff": 0.0} if exact else {"max_rel_err": rel_tol}),
    }  # fmt: skip
    lines = [f"{k}={numbers[k]!r} limit={v!r}" for k, v in limits.items()]
    return all(numbers[k] <= v for k, v in limits.items()), lines


def run_correct(answers_ok: bool, failed: int, attempted: int) -> bool:
    """A run's `correct`: every compared answer within its limits, and no more
    than 1 query in 100 failed (refused, timed out, answered in part). The
    traffic is chosen so that none fails; a broker that sheds most of a window
    answers the rest quickly, and such a window is not a measurement."""
    return bool(answers_ok) and failed <= attempted // 100
