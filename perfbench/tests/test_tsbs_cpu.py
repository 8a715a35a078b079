"""`tsbs_cpu`: the generator is a function of (seed, segment), the table has
TSBS cpu-only's 21 columns and time-ordered segments, and each template's
reference equals brute-force numpy over the decoded rows at a tiny scale.
The program's side of the same queries is `tests/test_group_key_expr.py`
and the rehearsal in `tests/test_served_path.py`."""

import json
import re

import numpy as np
import pytest

from perfbench import datagen, refeval
from perfbench.datasets import tsbs_cpu as ds
from perfbench.manifest import BENCH, load_cell, load_manifest

CFG = {"hosts": 7, "intervalSeconds": 10, "days": 2, "rows": 17_280 * 7, "segmentRows": 2_160 * 7}  # 8 segments of 6 hours
SEED = 3_350_000_123


def test_a_segment_is_a_function_of_seed_and_index():
    a, b = (ds.segment(SEED, 3, CFG["segmentRows"], CFG) for _ in range(2))
    other_index = ds.segment(SEED, 4, CFG["segmentRows"], CFG)
    other_seed = ds.segment(SEED + 1, 3, CFG["segmentRows"], CFG)
    for col in a:
        assert np.array_equal(a[col].codes, b[col].codes), col
        assert len(a[col].codes) == CFG["segmentRows"]
    for other in (other_index, other_seed):
        assert not np.array_equal(a["usage_user"].codes, other["usage_user"].codes)
    # a host keeps its tags from segment to segment, and another seed draws other tags
    assert all(np.array_equal(a[t].codes[:7], other_index[t].codes[:7]) for t in ds.TAGS)
    assert any(not np.array_equal(a[t].codes[:7], other_seed[t].codes[:7]) for t in ds.TAGS[1:])


def test_the_table_is_tsbs_cpu_only():
    """A timestamp, ten tags, ten DOUBLE metrics; `hour` is the reference's
    alone and `datagen.build_segment` never sees it; walks stay in [0, 100]
    and move by N(0,1) steps; rows are in time order, all hosts of an instant together."""
    seg = ds.segment(SEED, 0, CFG["segmentRows"], CFG)
    schema = [c for c, _, _ in ds.SCHEMA]
    assert len(schema) == 21 and schema == ["ts"] + ds.TAGS + ds.METRICS
    assert [t for _, t, _ in ds.SCHEMA] == ["LONG"] + ["STRING"] * 10 + ["DOUBLE"] * 10
    assert sorted(set(seg) - set(schema)) == ["hour"]
    ts = seg["ts"].values()
    assert ts[0] == ds.START_MS and np.all(np.diff(ts.reshape(-1, 7)[:, 0]) == 10_000) and np.all(ts.reshape(-1, 7).T == ts[::7])
    assert np.array_equal(seg["hour"].values(), ts // ds.HOUR_MS * ds.HOUR_MS)
    walk = seg["usage_idle"].codes.reshape(-1, 7)
    assert walk.dtype == np.float64 and walk.min() >= 0.0 and walk.max() <= 100.0
    inner = np.diff(walk, axis=0)[(walk[1:] > 0) & (walk[1:] < 100) & (walk[:-1] > 0) & (walk[:-1] < 100)]
    assert abs(inner.mean()) < 0.05 and abs(inner.std() - 1.0) < 0.05
    voc = ds.vocabs(CFG)
    for col, c in seg.items():
        if c.vocab is not None:
            assert np.array_equal(c.vocab, voc[col]) and np.all(c.vocab[:-1] < c.vocab[1:]), col
    built = datagen.build_segment(ds, seg, "cpu_0")  # the program's segment takes the 21 and is not shown the 22nd
    assert sorted(built.columns) == sorted(schema) and built.columns["ts"].dictionary.cardinality == 2_160


@pytest.mark.parametrize("name", list(ds.TEMPLATES))
def test_reference_equals_brute_force(name):
    """The Spec through refeval's partial / merge / finish against a loop over
    the decoded rows, for windows that begin mid-hour and on an hour's edge."""
    tpl = ds.TEMPLATES[name]
    parts = [ds.segment(SEED, i, CFG["segmentRows"], CFG) for i in range(8)]
    rows = {c: np.concatenate([p[c].values() for p in parts]) for c in ["ts", "hostname", *ds.METRICS]}
    rng = np.random.default_rng(11)
    for params in (tpl.draw(rng), {**tpl.draw(rng), "lo": ds.START_MS + 5 * ds.HOUR_MS, "hi": ds.START_MS + 17 * ds.HOUR_MS}):
        sql = tpl.render(params)
        metrics, k = params["metrics"], {"double-groupby-1": 1, "double-groupby-5": 5, "double-groupby-all": 10}[name]
        assert len(metrics) == len(set(metrics)) == k and set(metrics) <= set(ds.METRICS)
        assert f"ts >= {params['lo']} AND ts < {params['hi']}" in sql and ", ".join(f"AVG({m})" for m in metrics) + " FROM cpu" in sql
        assert params["hi"] - params["lo"] == 12 * ds.HOUR_MS and (params["lo"] - ds.START_MS) % 10_000 == 0
        merged = refeval.merge([refeval.partial(tpl.spec, params, p) for p in parts])
        got = refeval.finish(tpl.spec, merged, ds.vocabs(CFG))
        inside = (rows["ts"] >= params["lo"]) & (rows["ts"] < params["hi"])
        hour = rows["ts"] // ds.HOUR_MS * ds.HOUR_MS
        want = {}
        for host in np.unique(rows["hostname"]):
            for h in np.unique(hour[inside]):
                pick = inside & (rows["hostname"] == host) & (hour == h)
                want[(host, int(h))] = [float(rows[m][pick].mean()) for m in metrics]
        assert len(got) == len(want) and len(want) in (7 * 12, 7 * 13)
        for host, h, *means in got:
            np.testing.assert_allclose(means, want[(host, h)], rtol=1e-13)


def test_the_cell_is_what_the_issue_names():
    manifest = load_manifest()
    cell = load_cell(manifest, "tsbs-hosthour-closed")
    assert cell["entry"] == {**cell["entry"], "config": "tsbs-cpu-1srv", "traffic": "doublegroupby1-closed1", "chips": 1}
    cfg, traffic = cell["config"], cell["traffic"]
    assert (cfg["hosts"], cfg["rows"], cfg["segmentRows"], cfg["days"]) == (4000, 69_120_000, 4_320_000, 2)
    assert ds.steps(cfg) == 17_280 and ds.steps({**cfg, **cfg["rehearsal"]}) == 17_280
    assert traffic["loop"] == {"kind": "closed", "clients": 1} and traffic["templates"] == {"double-groupby-1": 1}
    assert (traffic["limit"], traffic["check"], traffic["trace"]) == (60_000, {"perTemplate": 2}, {"afterSeconds": 1, "seconds": 8})
    listed = [m["name"] for s in ("end_to_end", "per_layer") for m in manifest[s] if "tsbs-hosthour-closed" in m.get("workloads", [])]
    assert {"groupkey_plan_ms", "segments_pruned_share", "grouped_double_hbm_share"} <= set(listed) and "queries_per_s" not in listed
    assert json.loads((BENCH / "configs" / "tsbs-cpu-1srv.json").read_text())["name"] == cell["entry"]["config"]


def test_the_warm_up_statements_reach_every_segment():
    """A drawn 12-hour window reaches 4 or 5 of the 16 segments, and the program
    stages a segment at the first query that reaches it: the mix's
    `warmup.statements` are its own query over the table's four quarters, so
    that set-up stages the whole table and no first touch falls into the window."""
    traffic = load_cell(load_manifest(), "tsbs-hosthour-closed")["traffic"]
    one = ds.TEMPLATES["double-groupby-1"]
    spans = []
    for sql in traffic["warmup"]["statements"]:
        lo, hi = (int(x) for x in re.findall(r"ts [><]=? (\d+)", sql))
        assert sql == one.render({"lo": lo, "hi": hi, "avgs": "AVG(usage_user)"})  # the template's own text: no program of another shape
        spans.append((lo, hi))
    assert spans == [(ds.START_MS + k * 12 * ds.HOUR_MS, ds.START_MS + (k + 1) * 12 * ds.HOUR_MS) for k in range(ds.TABLE_HOURS // 12)]
    assert "segment_stage_ms" not in [m["name"] for m in load_manifest()["per_layer"] if "tsbs-hosthour-closed" in m.get("workloads", [])]


def test_a_query_draws_its_metrics_as_well_as_its_window():
    """`double-groupby-1` averages one metric of the ten and `-5` five of them,
    drawn with the window from the run's stream (ISSUE 35): a window's queries
    reach every metric, the same stream draws the same queries, `-all` has nothing to draw."""
    one, five, every = (ds.TEMPLATES[f"double-groupby-{k}"] for k in ("1", "5", "all"))
    draws = [one.draw(rng) for rng in [np.random.default_rng(7)] for _ in range(200)]
    assert {d["metrics"][0] for d in draws} == set(ds.METRICS) and len({d["lo"] for d in draws}) > 150
    assert draws == [one.draw(rng) for rng in [np.random.default_rng(7)] for _ in range(200)]
    assert len({tuple(five.draw(rng)["metrics"]) for rng in [np.random.default_rng(8)] for _ in range(50)}) > 40
    assert every.draw(np.random.default_rng(9))["metrics"] == ds.METRICS
    assert one.render(draws[0]).count("AVG(") == 1 and five.render(five.draw(np.random.default_rng(8))).count("AVG(") == 5


# -- the three readers the cell brings ---------------------------------------


class _Sample:
    def __init__(self, doc):
        self.doc = doc


def _run(docs, trace=None, config=None):
    good = [_Sample(d) for d in docs]
    return {"good": good, "samples": good, "trace": trace, "trace_window": (1.0, 9.0), "config": config or load_cell(load_manifest(), "tsbs-hosthour-closed")["config"]}


def test_readers_read_what_this_program_adds_and_nothing_of_a_program_without_it():
    from perfbench.layer_metrics import groupkey_plan_ms, grouped_double_hbm_share, segments_pruned_share

    def work(kernel):  # an answer's `deviceWork`: 4 launches of the full-segment program, 1 of the filtered one
        return {
            "seg_groupby_0a1b2c3d": {"launches": 4, "rows": 4 * 4_320_256, "kernels": {kernel: {"calls": 4, "bytes": 1.0, "flops": 1.0}}},
            "seg_groupby_4e5f6a7b": {"launches": 1, "rows": 4_320_256, "kernels": {kernel: {"calls": 1, "bytes": 1.0, "flops": 1.0}}},
        }

    with_it = [
        {"spanTimesMs": {"server.plan.group_key": 0.4, "server.execute": 900.0}, "numSegmentsPrunedByServer": 12,
         "deviceWork": work("query.grouped_scatter")},
        {"spanTimesMs": {"server.plan.group_key": 0.6, "server.execute": 950.0}, "numSegmentsPrunedByServer": 11,
         "deviceWork": work("query.grouped_scatter")},
    ]  # fmt: skip
    assert groupkey_plan_ms.read(_run(with_it)) == pytest.approx(0.5)
    assert segments_pruned_share.read(_run(with_it)) == pytest.approx(100 * 11.5 / 16)
    # no span, no prune count, the scatter under no name of its own
    parent = [{"spanTimesMs": {"server.execute": 900.0}, "deviceWork": work("query.grouped_dense")}]
    assert groupkey_plan_ms.read(_run(parent)) is None and segments_pruned_share.read(_run(parent)) is None
    # 9 launches of a 4.32M-row segment in the traced window, 3.5 s of the chip's time; another program beside them
    trace = {"modules": [["jit_seg_groupby_0a1b2c3d(1)", 2.8, 7], ["jit_seg_groupby_4e5f6a7b(3)", 0.7, 2], ["jit_seg_agg_99999999(2)", 0.5, 40]],
             "ops": [["fusion.1", 2.9], ["multiply_add_fusion", 0.2]], "chips": [{}], "busy_s": 4.0}  # fmt: skip
    per_launch = grouped_double_hbm_share.bytes_of_a_launch(_run([])["config"], 1)
    assert per_launch == 4_320_000 * 16 + 12_000 * 8
    assert grouped_double_hbm_share.read(_run(with_it, trace)) == pytest.approx(100 * 9 * per_launch / 3.5 / 819e9)
    assert grouped_double_hbm_share.read(_run(with_it, None)) is None
    assert grouped_double_hbm_share.read(_run(parent, trace)) is None
    assert grouped_double_hbm_share.read(_run(with_it, {**trace, "modules": trace["modules"][2:]})) is None
    # another configuration has no hosts and hours to count by
    assert grouped_double_hbm_share.read(_run(with_it, trace, {"rows": 60_000_000, "segmentRows": 4_000_000})) is None
