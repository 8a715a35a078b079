"""Star-tree index: pre-aggregation over a dimension split order.

Reference parity: StarTreeV2 (pinot-segment-spi/.../index/startree/), builders
(pinot-segment-local/.../startree/v2/builder/OffHeapSingleTreeBuilder), and
the query-side swap (StarTreeFilterOperator / StarTreeAggregationExecutor /
StarTreeGroupByExecutor, pinot-core/.../startree/executor/...:36,45).

TPU-native redesign: Pinot's star-tree exists to SKIP rows via tree traversal
on a CPU. On a TPU the same benefit comes from COMPACTION alone — we
materialize the leaf level (one row per distinct split-dimension combination,
carrying pre-aggregated values) as a dense columnar table that shares the
parent segment's dictionaries. A matching query then runs the ordinary fused
filter/group-by program over ~cardinality-product rows instead of n_docs
rows; predicates lower to the same dict-id compares, and aggregations rewrite
onto the pre-aggregated columns (COUNT -> SUM(__count), SUM(x) ->
SUM(sum__x), MIN(x) -> MIN(min__x), ...). No pointer-chasing, no
tree-specific kernels, full reuse of the query compiler.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np
import pandas as pd

from pinot_tpu.common.config import StarTreeIndexConfig
from pinot_tpu.common.types import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.segment.segment import ColumnIndex, ImmutableSegment
from pinot_tpu.segment.stats import ColumnStats

# aggregation functions derivable from each stored pre-agg column kind
_STORED_FUNCS = ("sum", "min", "max")


@dataclass
class StarTable:
    """One pre-aggregated table (the leaf level of one star-tree config)."""

    dimensions: list[str]  # split order
    function_column_pairs: list[str]  # e.g. "SUM__revenue"
    n_rows: int
    # dict-id columns per dimension + value columns per pair + __count
    arrays: dict[str, np.ndarray] = field(default_factory=dict)

    def supports_agg(self, func: str, arg_col: str | None) -> bool:
        if func == "count":
            return True
        if func in ("sum", "avg"):
            return f"SUM__{arg_col}" in self.function_column_pairs
        if func == "min":
            return f"MIN__{arg_col}" in self.function_column_pairs
        if func == "max":
            return f"MAX__{arg_col}" in self.function_column_pairs
        if func == "minmaxrange":
            return (
                f"MIN__{arg_col}" in self.function_column_pairs
                and f"MAX__{arg_col}" in self.function_column_pairs
            )
        if func in ("distinctcount", "distinctcountbitmap", "distinctcounthll"):
            # distinct over a split dimension is presence-preserving
            return arg_col in self.dimensions
        return False


def build_star_table(seg: ImmutableSegment, config: StarTreeIndexConfig) -> StarTable:
    """Leaf-level pre-aggregation: group by all split dimensions' dict ids,
    aggregate the configured function-column pairs (MultipleTreesBuilder
    analog, vectorized)."""
    dims = config.dimensions_split_order
    for d in dims:
        ci = seg.columns.get(d)
        if ci is None or not ci.is_dict_encoded:
            raise ValueError(f"star-tree dimension {d!r} must be a dict-encoded column")
    def _norm(p: str) -> str:
        func, col = p.split("__", 1)
        return f"{func.upper()}__{col}"  # uppercase the FUNC, preserve the column

    # COUNT__* (Pinot's AggregationFunctionColumnPair.COUNT_STAR) is served by
    # the always-present __count column; accept and drop it from the pair list.
    pairs = list(
        dict.fromkeys(
            _norm(p) for p in config.function_column_pairs if not _norm(p).startswith("COUNT__")
        )
    )
    df = pd.DataFrame({d: seg.columns[d].forward for d in dims})
    needed_cols = {}
    for p in pairs:
        func, col = p.split("__", 1)
        if col not in seg.columns:
            raise ValueError(f"star-tree pair {p}: unknown column {col!r}")
        if col not in needed_cols:
            # an INT / LONG source accumulates and is stored as int64: exact by construction (a float64 pair
            # holds a sum only up to 2^53); FLOAT and DOUBLE sources stay float64
            ci = seg.columns[col]
            acc = np.int64 if ci.data_type in (DataType.INT, DataType.LONG) else np.float64
            needed_cols[col] = ci.materialize().astype(acc)
    for col, vals in needed_cols.items():
        df[f"v::{col}"] = vals

    g = df.groupby(dims, sort=True)
    out = g.size().rename("__count").reset_index()
    # ... unless a record's sum could leave int64 (a LONG null's placeholder is its minimum): numpy would wrap
    # where the scan, which adds such a column as float64, only rounds. Those sums are accumulated as float64
    most = int(out["__count"].max()) if len(out) else 0

    def may_wrap(col: str) -> bool:
        stats = seg.columns[col].stats
        return max(abs(int(stats.min_value)), abs(int(stats.max_value))) * most >= 2**63

    wraps = {col for col, vals in needed_cols.items() if vals.dtype == np.int64 and may_wrap(col)}
    arrays: dict[str, np.ndarray] = {"__count": out["__count"].to_numpy(np.int64)}
    for d in dims:
        arrays[d] = out[d].to_numpy(np.int32)
    for p in pairs:
        func, col = p.split("__", 1)
        vals = g[f"v::{col}"]
        if func in ("SUM", "AVG"):
            # AVG pair stores SUM (count comes from __count), like Pinot's
            # AvgPair value aggregator
            if col in wraps:  # the same records in the same order: the keys sorted
                vals = pd.Series(needed_cols[col].astype(np.float64)).groupby([df[d] for d in dims], sort=True)
            arrays[f"SUM__{col}"] = vals.sum().to_numpy()
        elif func == "MIN":
            arrays[p] = vals.min().to_numpy()
        elif func == "MAX":
            arrays[p] = vals.max().to_numpy()
        else:
            raise ValueError(f"unsupported star-tree aggregation {func}")
    pairs = [p for p in arrays if "__" in p and not p.startswith("__")]
    return StarTable(dimensions=list(dims), function_column_pairs=pairs, n_rows=len(out), arrays=arrays)


def star_table_as_segment(seg: ImmutableSegment, st: StarTable) -> ImmutableSegment:
    """Wrap a StarTable as an engine-queryable segment: dimension columns
    share the parent's dictionaries; pre-agg columns are raw metrics, LONG
    where the pair is stored as integers and DOUBLE otherwise (every pair of
    a table persisted before integer pairs were kept)."""
    schema = Schema(seg.schema.name + "__star")
    star = ImmutableSegment(name=seg.name + "__star", schema=schema, n_docs=st.n_rows)
    for d in st.dimensions:
        parent = seg.columns[d]
        ids = st.arrays[d]
        schema.add(FieldSpec(d, parent.data_type, FieldType.DIMENSION))
        stats = ColumnStats.from_dictionary(d, parent.data_type, ids, parent.dictionary)
        star.columns[d] = ColumnIndex(d, parent.data_type, parent.dictionary, ids, stats)
    for name in ["__count", *st.function_column_pairs]:
        vals = st.arrays[name]
        dt = DataType.LONG if vals.dtype.kind == "i" else DataType.DOUBLE
        schema.add(FieldSpec(name, dt, FieldType.METRIC))
        stats = ColumnStats.collect(name, dt, vals, len(np.unique(vals)))
        star.columns[name] = ColumnIndex(name, dt, None, vals.astype(dt.np_dtype), stats)
    return star
