"""The reach of `kernels._gather_rows`: only a plan with a `lookUp` node or an
expression GROUP BY key enters it. Six of the benchmark's cells hold neither
(`ssb-groupby-closed`, `ssb-q1-rate`, `ssb-citygroups-closed`,
`ssb4-groupby-closed`, `ssb4-serverloss-closed` over the flat SSB table,
`tpch-q1q6-closed`): with the helper patched to raise, every template of
theirs — read from the cells' own traffic and dataset files — still traces
and runs on the device path. So a change to the helper cannot move those
cells' programs, and a reader of a refusal in one of them can rule the device
program out at once.

And the reach of the compact group space (plan.group_spec's "groups_compact",
PR 45): it is chosen where the keys' product reaches plan.COMPACT_MIN_GROUPS,
so of the cells' templates only SSB Q3.2-Q3.4 (and Q4.3, in no cell) change
their program; the group spec and program name of the others are written
down here as the parent commit gave them (a star-table plan's beside
`tests/test_startree_swap.py`'s SSB table, a lookUp plan's in
`tests/test_lookup_device.py`).
"""

import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import datagen, manifest, tables
from pinot_tpu.common.trace import request_ledger
from pinot_tpu.query import QueryEngine, kernels
from pinot_tpu.query.plan import COMPACT_MIN_GROUPS, COMPACT_SLOTS, plan_segment

ROOT = Path(__file__).resolve().parent.parent

#: the cells whose traffic bypasses the gather
BYPASS = ("ssb-groupby-closed", "ssb-q1-rate", "tpch-q1q6-closed", "ssb-citygroups-closed", "ssb4-groupby-closed", "ssb4-serverloss-closed")
GATHERS = ("query.lookup_gather", "query.key_gather")
SEED, ROWS = 4_300_000_001, 3_000


def _templates(cells) -> list:
    """(dataset, template) of the cells' configurations and traffic files, each pair once."""
    bench = manifest.load_manifest()
    loaded = [manifest.load_cell(bench, name) for name in cells]
    sent = dict.fromkeys((cell["config"]["dataset"], t) for cell in loaded for t in cell["traffic"]["templates"])
    return [pytest.param(ds, t, id=f"{ds}-{t}") for ds, t in sent]


@pytest.fixture(scope="module")
def segments():
    """A rehearsal-sized segment a dataset, built as the harness builds it."""
    built: dict = {}

    def segment(dataset: str):
        if dataset not in built:
            configs = (json.loads(p.read_text()) for p in sorted((ROOT / "perfbench" / "configs").glob("*.json")))
            config = next(c for c in configs if c["dataset"] == dataset)
            tables.rehearse(config)
            ds = datagen.dataset_module(dataset)
            built[dataset] = (ds, datagen.build_segment(ds, ds.segment(SEED, 0, ROWS, config), f"{ds.TABLE}_0"))
        return built[dataset]

    return segment


@pytest.fixture
def no_gather(monkeypatch):
    """`_gather_rows` raises, and every packed program is traced afresh under it."""

    def refuse(*_a, **_k):
        raise AssertionError("_gather_rows was entered")

    monkeypatch.setattr(kernels, "_gather_rows", refuse)
    kernels.get_packed_kernel.cache_clear()
    yield
    kernels.get_packed_kernel.cache_clear()


@pytest.mark.parametrize("dataset, template", _templates(BYPASS))
def test_a_plan_with_no_lookup_and_no_expression_key_never_enters_the_gather(dataset, template, segments, no_gather):
    ds, seg = segments(dataset)
    t = ds.TEMPLATES[template]
    sql = t.render(t.draw(np.random.default_rng(SEED)))
    assert "lookup" not in sql.lower()
    with request_ledger(f"reach-{template}") as led:
        res = QueryEngine([seg]).execute(sql)
    assert res.rows is not None
    work = led.to_wire()["deviceWork"]
    assert work and sum(w["launches"] for w in work.values()) == 1, work  # the device path, one fused program over the segment
    assert not [k for w in work.values() for k in w["kernels"] if k in GATHERS], work


def test_the_patch_bites_where_a_key_is_an_expression(segments, no_gather):
    """The control: `tsbs-hosthour-closed`'s template does enter the helper
    (its window set to the table's first twelve hours, where the one segment lies)."""
    ds, seg = segments("tsbs_cpu")
    t = ds.TEMPLATES["double-groupby-1"]
    params = {**t.draw(np.random.default_rng(SEED)), "lo": ds.START_MS, "hi": ds.START_MS + ds.WINDOW_HOURS * ds.HOUR_MS}
    with pytest.raises(AssertionError, match="_gather_rows was entered"):
        QueryEngine([seg]).execute(t.render(params))


#: (dataset, template) -> (group spec, program name) of its plan over the rehearsal-sized segment, as PR 45's parent
#: commit gave them: every group-by under plan.COMPACT_MIN_GROUPS keeps its spec, and with it its program and compile-cache key
PARENT_PLANS = {
    ("ssb_flat", "q2.1"): (("groups", ("d_year", "p_brand1"), 6912, 4), "seg_groupby_7ecc01b9"),
    ("ssb_flat", "q3.1"): (("groups", ("c_nation", "s_nation", "d_year"), 4608, 6), "seg_groupby_e190ae97"),
    ("ssb_flat", "q4.1"): (("groups", ("d_year", "c_nation"), 256, 5), "seg_groupby_443f5770"),
    ("tsbs_cpu", "double-groupby-1"): (("groups", ("hostname", ("remap", "ts", 0)), 256, 1, 40), "seg_groupby_30d8448a"),
}


def _plan(segments, dataset: str, template: str):
    ds, seg = segments(dataset)
    t = ds.TEMPLATES[template]
    params = t.draw(np.random.default_rng(SEED))
    if dataset == "tsbs_cpu":  # the window set to where the one segment lies, as in the control above
        params = {**params, "lo": ds.START_MS, "hi": ds.START_MS + ds.WINDOW_HOURS * ds.HOUR_MS}
    return plan_segment(seg, QueryEngine([seg]).make_context(t.render(params)))


@pytest.mark.parametrize("dataset, template", [pytest.param(*k, id="-".join(k)) for k in PARENT_PLANS])
def test_a_group_by_under_the_compact_threshold_keeps_the_parents_spec_and_program(dataset, template, segments):
    plan = _plan(segments, dataset, template)
    assert (plan.spec[2], kernels.program_name(plan.spec)) == PARENT_PLANS[dataset, template]
    assert plan.spec[2][2] < COMPACT_MIN_GROUPS


@pytest.mark.parametrize("template", ["q3.2", "q3.3", "q3.4", "q4.3"])
def test_the_city_level_flights_take_the_compact_group_space(template, segments):
    """250 x 250 x 7 (and Q4.3's 7 x 250 x 1000) dense groups: each key renumbered, 4,096 slots."""
    kind, keys, slots, _, widths = _plan(segments, "ssb_flat", template).spec[2]
    assert (kind, slots) == ("groups_compact", COMPACT_SLOTS) and all(how == "rank" for how, _ in widths)
    assert dict(zip(keys, widths))["d_year"] == ("rank", 8) and np.prod([w for _, w in widths]) >= COMPACT_MIN_GROUPS
