"""The datagen's direct assembly of a segment equals what the program's own
SegmentBuilder makes of the same rows: same dictionaries, same forward
indexes, same statistics, and the same answers from the engine."""

import importlib

import numpy as np
import pytest

from perfbench import datagen

CFG = {"scaleFactor": 1}


@pytest.mark.parametrize("name", ["ssb_flat", "tpch_lineitem"])
def test_the_direct_segment_equals_the_builders(name):
    from pinot_tpu.segment import SegmentBuilder

    ds = importlib.import_module(f"perfbench.datasets.{name}")
    cols = ds.segment(2_200_000_001, 0, 3_000, CFG)
    direct = datagen.build_segment(ds, cols, "seg_0")
    raw = {}
    for c, col in cols.items():
        v = col.values()
        raw[c] = v.astype(object) if v.dtype.kind == "U" else v
    built = SegmentBuilder(datagen.program_schema(ds)).build(raw, "seg_0")
    assert list(direct.columns) == list(built.columns)
    for c in direct.columns:
        a, b = direct.columns[c], built.columns[c]
        assert a.is_dict_encoded == b.is_dict_encoded, c
        assert a.forward.dtype == b.forward.dtype and np.array_equal(a.forward, b.forward), c
        if a.is_dict_encoded:
            assert np.array_equal(a.dictionary.values, b.dictionary.values), c
        assert a.stats.to_dict() == b.stats.to_dict(), c
