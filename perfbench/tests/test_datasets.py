"""The generators are deterministic per (seed, segment); each template's
reference equals brute-force pandas over the decoded rows at a tiny scale."""

import importlib

import numpy as np
import pandas as pd
import pytest

from perfbench import refeval

CASES = {
    "ssb_flat": {"scaleFactor": 1, "rows": 60_000, "segmentRows": 20_000},
    "tpch_lineitem": {"scaleFactor": 1, "rows": 60_000, "segmentRows": 20_000},
}


def load(name):
    return importlib.import_module(f"perfbench.datasets.{name}")


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_segment_is_a_function_of_seed_and_index(name):
    ds, cfg = load(name), CASES[name]
    a = ds.segment(2_500_000_123, 1, 5_000, cfg)
    b = ds.segment(2_500_000_123, 1, 5_000, cfg)
    other_index = ds.segment(2_500_000_123, 2, 5_000, cfg)
    other_seed = ds.segment(2_500_000_124, 1, 5_000, cfg)
    assert list(a) == [c for c, _, _ in ds.SCHEMA]
    for col in a:
        assert np.array_equal(a[col].codes, b[col].codes), col
        assert len(a[col].codes) == 5_000
    differs = lambda x, y: any(not np.array_equal(x[c].codes, y[c].codes) for c in x)  # noqa: E731
    assert differs(a, other_index) and differs(a, other_seed)


@pytest.mark.parametrize("name", sorted(CASES))
def test_coded_columns_index_sorted_vocabularies_shared_by_every_segment(name):
    ds, cfg = load(name), CASES[name]
    voc = ds.vocabs(cfg)
    seg = ds.segment(7, 0, 5_000, cfg)
    for col, c in seg.items():
        if c.vocab is None:
            continue
        assert np.array_equal(c.vocab, voc[col])
        assert np.all(c.vocab[:-1] < c.vocab[1:]), f"{col}: vocabulary not sorted and unique"
        assert c.codes.min() >= 0 and c.codes.max() < len(c.vocab)


def frame(ds, cfg, seed, n_segments, n):
    parts = [ds.segment(seed, i, n, cfg) for i in range(n_segments)]
    return parts, pd.DataFrame({c: np.concatenate([p[c].values() for p in parts]) for c in parts[0]})


def pandas_answer(t: pd.DataFrame, sql: str) -> list[list]:
    """The template's SQL, brute force: a WHERE via DataFrame.query and a
    groupby, written from the SQL text alone (not from the Spec)."""
    head, _, rest = sql.partition(" FROM ")
    where = rest.split(" WHERE ")[1].split(" GROUP BY ")[0].split(" ORDER BY ")[0].split(" LIMIT ")[0]
    where = where.replace(" AND ", " and ").replace(" OR ", " or ").replace(" = ", " == ")
    import re

    where = re.sub(r"(\w+) BETWEEN ('[^']*'|[\d.]+) and ('[^']*'|[\d.]+)", r"(\1 >= \2 and \1 <= \3)", where)
    rows = t.query(where)
    keys = rest.split(" GROUP BY ")[1].split(" ORDER BY ")[0].split(", ") if " GROUP BY " in rest else []
    out_cols = []
    for item in re.split(r", (?![^(]*\))", head[len("SELECT "):]):
        m = re.fullmatch(r"(SUM|AVG|COUNT)\((.*)\)", item)
        out_cols.append((m.group(1), m.group(2)) if m else ("KEY", item))
    groups = rows.groupby(keys) if keys else [((), rows)]
    out = []
    for key, g in groups:
        key = key if isinstance(key, tuple) else (key,)
        row = []
        for kind, expr in out_cols:
            if kind == "KEY":
                v = key[keys.index(expr)]
                row.append(v.item() if hasattr(v, "item") else v)
            elif kind == "COUNT":
                row.append(float(len(g)))
            else:
                vals = g.eval(expr).astype(np.float64)
                row.append(float(vals.sum() if kind == "SUM" else vals.mean()))
        out.append(row)
    return out


@pytest.mark.parametrize("name,template", [(n, t) for n in sorted(CASES) for t in sorted(load(n).TEMPLATES)])
def test_the_reference_of_a_template_equals_brute_force_pandas(name, template):
    ds, cfg = load(name), CASES[name]
    parts, table = frame(ds, cfg, seed=2_147_483_700, n_segments=3, n=20_000)
    tpl = ds.TEMPLATES[template]
    rng = np.random.default_rng(11)
    compared = 0
    for draw in range(200):  # four parameter sets, and more until one matches a row (Q3.4 is that selective)
        if draw >= 4 and compared:
            break
        params = tpl.draw(rng)
        merged = refeval.merge([refeval.partial(tpl.spec, params, p) for p in parts])
        got = sorted(refeval.finish(tpl.spec, merged, ds.vocabs(cfg)), key=str)
        want = sorted(pandas_answer(table, tpl.render(params)), key=str)
        if not tpl.spec.keys and merged["n"] == 0:
            continue  # an empty sum: pandas says 0.0, the reference keeps the one row; nothing to compare
        assert len(got) == len(want), (template, params)
        for g, w in zip(got, want):
            for a, b in zip(g, w):
                if isinstance(b, float):
                    assert a == pytest.approx(b, rel=1e-12), (template, params, g, w)
                else:
                    assert a == b, (template, params, g, w)
        compared += len(want)
    assert compared > 0, f"{template}: no drawn parameter set matched a row at this scale"


def test_every_seed_sends_the_same_templates_in_the_same_shares():
    from perfbench import loadgen

    ds = load("ssb_flat")
    weights = {"q1.1": 1, "q1.2": 1, "q1.3": 2}
    a = loadgen.draw_queries(ds.TEMPLATES, weights, np.random.default_rng([5, 1]), 400)
    b = loadgen.draw_queries(ds.TEMPLATES, weights, np.random.default_rng([6, 1]), 400)
    count = lambda qs, t: sum(1 for q in qs if q.template == t)  # noqa: E731
    for t in weights:
        assert count(a, t) == count(b, t) == 400 * weights[t] // 4
    assert [q.sql for q in a] != [q.sql for q in b]
    again = loadgen.draw_queries(ds.TEMPLATES, weights, np.random.default_rng([5, 1]), 400)
    assert [q.sql for q in a] == [q.sql for q in again]


def test_every_seed_offers_the_same_arrivals_in_another_order():
    from perfbench import loadgen

    loop = {"kind": "open", "arrivals": "poisson", "rate": 13.0, "levelSeconds": 1.0}
    a = loadgen.arrival_times(loop, 45.0, np.random.default_rng(1))
    b = loadgen.arrival_times(loop, 45.0, np.random.default_rng(2))
    assert len(a) == len(b) == 585 and a[0] == 0.0 and a[-1] < 45.0
    # every stretch of a second offers the same load
    assert set(np.histogram(a, bins=45, range=(0, 45))[0]) <= {12, 13, 14}
    # the same gaps (the quantiles of one second's 13), whatever the seed; only the first, cut off, may differ
    gaps_a, gaps_b = np.unique(np.round(np.diff(a), 9)), np.unique(np.round(np.diff(b), 9))
    assert len(gaps_a) == 13 and np.allclose(gaps_a, gaps_b, rtol=2e-2)
    assert not np.allclose(np.diff(a), np.diff(b))
    # exponential gaps: the standard deviation is about the mean
    assert np.std(np.diff(a)) == pytest.approx(np.mean(np.diff(a)), rel=0.1)
    u = loadgen.arrival_times({"kind": "open", "arrivals": "uniform", "rate": 10.0}, 2.0, np.random.default_rng(1))
    assert np.allclose(np.diff(u), np.diff(u)[0]) and len(u) == 20
