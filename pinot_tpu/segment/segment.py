"""In-memory segment representations.

Host side: `ImmutableSegment` — numpy forward arrays + dictionaries + stats
(reference parity: ImmutableSegmentImpl, pinot-segment-local/.../indexsegment/
immutable/ImmutableSegmentImpl.java:67, and DataSource/ForwardIndexReader from
pinot-segment-spi).

Device side: `DeviceSegment` — the TPU-native redesign. Instead of Pinot's
off-heap buffers + batched `readValuesSV` decode (ForwardIndexReader.java:156),
a segment IS a pytree of dense device arrays: dict-encoded columns as int32 id
vectors, raw columns as native-dtype vectors, padded to a lane-friendly length.
Filters become vector compares over these arrays; there is no row-at-a-time or
block-at-a-time decode step to accelerate because the columnar data is already
resident in HBM in compute layout.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import numpy as np

from pinot_tpu.common.types import DataType, Schema
from pinot_tpu.segment.dictionary import Dictionary
from pinot_tpu.segment.stats import ColumnStats

# Pad doc counts to a multiple of the f32 tile (8 sublanes x 128 lanes) so XLA
# never sees ragged vectors. Padded tail rows are masked out by the engine via
# iota < n_docs.
DOC_PAD = 1024


def padded_len(n_docs: int) -> int:
    return max(DOC_PAD, ((n_docs + DOC_PAD - 1) // DOC_PAD) * DOC_PAD)


@dataclass
class ColumnIndex:
    """All materialized per-column data for one segment column.

    Multi-value columns (reference: the MV read API of ForwardIndexReader,
    pinot-segment-spi/.../index/reader/ForwardIndexReader.java:200-332) use a
    flattened CSR layout — `forward` holds ALL values back to back and `lens`
    the per-doc value counts. On device this keeps every kernel a dense 1-D
    op: predicates evaluate over the flat vector and scatter-max into doc
    space; MV aggregations gather the doc mask to value positions."""

    name: str
    data_type: DataType
    dictionary: Dictionary | None  # None => raw-encoded column
    forward: np.ndarray  # int32 dict ids, or raw values (np dtype of the type)
    stats: ColumnStats
    lens: np.ndarray | None = None  # MV only: int32 per-doc value count

    @property
    def is_dict_encoded(self) -> bool:
        return self.dictionary is not None

    @property
    def is_mv(self) -> bool:
        return self.lens is not None

    @property
    def cardinality(self) -> int:
        return self.dictionary.cardinality if self.dictionary else self.stats.cardinality

    def offsets(self) -> np.ndarray:
        """MV: value-range start offsets per doc, length n_docs+1."""
        out = np.zeros(len(self.lens) + 1, dtype=np.int64)
        np.cumsum(self.lens, out=out[1:])
        return out

    def flat_docids(self) -> np.ndarray:
        """MV: owning doc id per flat value position (int32)."""
        return np.repeat(
            np.arange(len(self.lens), dtype=np.int32), self.lens
        )

    def materialize(self, doc_ids: np.ndarray | None = None) -> np.ndarray:
        """Decode to raw values (optionally only for given docIds). MV columns
        return an object array of per-doc value arrays."""
        if self.is_mv:
            flat = (
                self.dictionary.get_many(self.forward)
                if self.dictionary is not None
                else self.forward
            )
            off = self.offsets()
            docs = range(len(self.lens)) if doc_ids is None else np.asarray(doc_ids)
            out = np.empty(len(off) - 1 if doc_ids is None else len(docs), dtype=object)
            for i, d in enumerate(docs):
                out[i] = flat[off[d] : off[d + 1]]
            return out
        fwd = self.forward if doc_ids is None else self.forward[doc_ids]
        if self.dictionary is not None:
            return self.dictionary.get_many(fwd)
        return fwd


@dataclass
class ImmutableSegment:
    name: str
    schema: Schema
    n_docs: int
    columns: dict[str, ColumnIndex] = field(default_factory=dict)
    # extra index structures (star-tree, bloom, ...) attach here in later layers
    extras: dict[str, Any] = field(default_factory=dict)

    def column(self, name: str) -> ColumnIndex:
        if name not in self.columns:
            raise KeyError(f"segment {self.name} has no column {name!r}")
        return self.columns[name]

    @property
    def size_bytes(self) -> int:
        """Resident host-memory estimate (forward arrays + dictionaries);
        feeds resource accounting the way segment sizes feed the reference's
        memory accountant."""
        total = 0
        for ci in self.columns.values():
            fwd = getattr(ci, "forward", None)
            if isinstance(fwd, np.ndarray):
                total += fwd.nbytes
            d = getattr(ci, "dictionary", None)
            vals = getattr(d, "values", None)
            if isinstance(vals, np.ndarray) and vals.dtype != object:
                total += vals.nbytes
        return total

    def declared_indexes(self) -> dict[str, list[str]]:
        """Per-column declared index classes (scan-path attribution &
        debug surfaces): which structures exist for each column, regardless
        of whether a given query/mode actually uses them.  Geo entries keep
        their composite "lat,lng" key."""
        out: dict[str, list[str]] = {}

        def add(col: str, cls: str) -> None:
            out.setdefault(col, []).append(cls)

        for col, ci in self.columns.items():
            if ci.is_dict_encoded and not ci.is_mv and getattr(ci.stats, "is_sorted", False):
                add(col, "SORTED_INDEX")
        for extras_key, cls in (
            ("inverted", "INVERTED_INDEX"),
            ("range", "RANGE_INDEX"),
            ("bloom", "BLOOM_FILTER"),
            ("fst", "FST_INDEX"),
            ("null", "NULL_INDEX"),
            ("text", "TEXT_INDEX"),
            ("json", "JSON_INDEX"),
            ("vector", "VECTOR_INDEX"),
            ("geo", "GEO_INDEX"),
        ):
            for col in self.extras.get(extras_key) or {}:
                add(col, cls)
        return out

    def to_device_cached(self) -> "DeviceSegment":
        """Memoized default staging (fast32=False). Callers outside a
        QueryEngine (e.g. the multistage leaf Scan) share one staged copy per
        segment instead of re-uploading columns every query."""
        ds = getattr(self, "_device_cache", None)
        if ds is None:
            ds = self.to_device()
            self._device_cache = ds
        return ds

    def to_device(self, fast32: bool = False) -> "DeviceSegment":
        """Stage to device memory.

        Dtype policy: int64 raw columns are losslessly narrowed to int32 when
        their min/max fit (cheaper lanes everywhere). float64 stays float64 —
        the TPU emulates f64 and query semantics (Pinot DOUBLE) depend on it —
        unless `fast32` opts into lossy float32 storage for speed.
        """
        from pinot_tpu.common.trace import count, span

        # the staging on the host's clock: what this thread spends handing the columns over; what the
        # transfer still owes when it returns is waited for by the first launch that reads them
        with span("server.stage", segment=self.name, columns=len(self.columns)) as stage:
            arrays = self._stage_columns(fast32)
            stage.set_attr("bytes", sum(int(a.nbytes) for a in arrays.values()))
        count("segmentsStaged")
        ds = DeviceSegment(
            name=self.name, host=self, n_docs=self.n_docs, padded=padded_len(self.n_docs), arrays=arrays
        )
        from pinot_tpu.common.leakcheck import staging_tracker

        staging_tracker.track(ds)  # HBM staging leak detection (test harness)
        return ds

    def _stage_columns(self, fast32: bool) -> dict[str, Any]:
        import jax.numpy as jnp

        pad = padded_len(self.n_docs)
        arrays: dict[str, Any] = {}
        for name, ci in self.columns.items():
            fwd = ci.forward
            if ci.is_mv:
                # flattened MV: flat value vector + owning-doc-id vector, both
                # padded to the doc-pad granule. Padding docids point one past
                # the padded doc range: scatters drop them, and gathers through
                # them are masked by the per-plan n_values operand.
                vpad = padded_len(len(fwd))
                docids = ci.flat_docids()
                docids = np.concatenate(
                    [docids, np.full(vpad - len(docids), pad, dtype=np.int32)]
                )
                if len(fwd) < vpad:
                    fwd = np.concatenate([fwd, np.zeros(vpad - len(fwd), dtype=fwd.dtype)])
                if narrows_to_int32(ci):
                    fwd = fwd.astype(np.int32)
                arrays[name] = jnp.asarray(fwd)
                arrays[f"{name}!docs"] = jnp.asarray(docids)
                continue
            if len(fwd) < pad:
                fwd = np.concatenate([fwd, np.zeros(pad - len(fwd), dtype=fwd.dtype)])
            dt = fwd.dtype
            if narrows_to_int32(ci):
                # dict ids are already int32; this is the raw-column path
                fwd = fwd.astype(np.int32)
            elif dt == np.float64 and fast32:
                fwd = fwd.astype(np.float32)
            arrays[name] = jnp.asarray(fwd)
        return arrays


def narrows_to_int32(ci: ColumnIndex) -> bool:
    """to_device's lossless narrowing: an int64 forward array whose min and
    max fit int32 is staged as int32. The plan asks the same question where
    an operand's dtype has to equal the device's (plan.py)."""
    i32 = np.iinfo(np.int32)
    return ci.forward.dtype == np.int64 and i32.min <= ci.stats.min_value and ci.stats.max_value <= i32.max


@dataclass
class DeviceSegment:
    """A segment staged in device memory: pytree of dense columnar arrays."""

    name: str
    host: ImmutableSegment
    n_docs: int
    padded: int
    arrays: dict[str, Any]  # column -> jnp.ndarray of shape (padded,)

    def array(self, col: str):
        return self.arrays[col]

    @property
    def schema(self) -> Schema:
        return self.host.schema
