"""Datagen child: generated columns -> the program's segments -> the controller.

The only benchmark code besides `server_main.py` that imports the program.
It runs in worker processes pinned to the CPU (`JAX_PLATFORMS=cpu` in their
environment), never in the launcher.

A generated column already is what a dictionary-encoded forward index holds
(codes into a sorted vocabulary), so the segment is assembled from the
program's own `Dictionary` / `ColumnStats` / `ColumnIndex` classes directly.
`SegmentBuilder.build` would rebuild the same dictionaries from 4M Python
strings a column; `tests/test_datagen.py` holds the two paths equal.
"""

from __future__ import annotations

import importlib
import io
import shutil
import tarfile
import time
from pathlib import Path

import numpy as np


def dataset_module(name: str):
    return importlib.import_module(f"perfbench.datasets.{name}")


def program_schema(ds):
    from pinot_tpu.common import DataType, Schema

    return Schema.build(
        ds.TABLE,
        dimensions=[(c, DataType[t]) for c, t, role in ds.SCHEMA if role == "dimension"],
        metrics=[(c, DataType[t]) for c, t, role in ds.SCHEMA if role == "metric"],
    )


def build_segment(ds, cols: dict, name: str):
    """An ImmutableSegment with the program's default encodings: dimensions
    dictionary-encoded over the values present in this segment, metrics raw."""
    from pinot_tpu.common import DataType
    from pinot_tpu.segment.dictionary import Dictionary
    from pinot_tpu.segment.segment import ColumnIndex, ImmutableSegment
    from pinot_tpu.segment.stats import ColumnStats

    schema = program_schema(ds)
    n = len(next(iter(cols.values())).codes)
    seg = ImmutableSegment(name=name, schema=schema, n_docs=n)
    kinds = {c: (DataType[t], role) for c, t, role in ds.SCHEMA}
    for col in schema.columns:  # the schema's order: dimensions, then metrics
        dt, role = kinds[col]
        codes, vocab = cols[col]
        if role == "metric":
            vals = np.ascontiguousarray(codes, dtype=dt.np_dtype)
            stats = ColumnStats.collect(col, dt, vals, len(np.unique(vals)))
            seg.columns[col] = ColumnIndex(col, dt, None, vals, stats)
            continue
        if vocab is None:  # a raw dimension (a key): its dictionary is its own values
            values, ids = np.unique(codes, return_inverse=True)
        else:  # keep the vocabulary's entries that occur here, codes renumbered
            present = np.bincount(codes, minlength=len(vocab)) > 0
            ids = (np.cumsum(present) - 1)[codes]
            values = vocab[present]
        if dt != DataType.STRING:
            values = values.astype(dt.np_dtype)
        dictionary = Dictionary(dt, values)
        ids = ids.astype(np.int32)
        seg.columns[col] = ColumnIndex(col, dt, dictionary, ids, ColumnStats.from_dictionary(col, dt, ids, dictionary))
    return seg


def build_and_upload(job: dict) -> dict:
    """One segment: generate, assemble, write as .ptseg, push to the controller."""
    from pinot_tpu.cluster.http import RemoteControllerClient
    from pinot_tpu.segment.builder import write_segment

    t0 = time.perf_counter()
    ds = dataset_module(job["dataset"])
    cols = ds.segment(job["seed"], job["index"], job["rows"], job["config"])
    t_gen = time.perf_counter()
    seg = build_segment(ds, cols, f"{ds.TABLE}_{job['index']}")
    del cols  # a 4M-row, 30-column segment is a gigabyte as generated; the machine has 40 GiB for everything
    seg_dir = Path(write_segment(seg, job["out_dir"]))
    name = seg.name
    del seg
    t_build = time.perf_counter()
    nbytes = sum(f.stat().st_size for f in seg_dir.iterdir())
    # the controller's tar.gz upload endpoint; the file is LZ4 chunks already,
    # so the lightest gzip level only frames it
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w:gz", compresslevel=1) as tf:
        tf.add(seg_dir, arcname=seg_dir.name)
    body = buf.getvalue()
    del buf
    RemoteControllerClient(job["controller_url"])._post(f"/segments/{ds.TABLE}", body, "application/gzip")
    shutil.rmtree(seg_dir)
    t_up = time.perf_counter()
    return {
        "segment": name, "rows": job["rows"], "fileBytes": nbytes,
        "gen_s": t_gen - t0, "build_s": t_build - t_gen, "upload_s": t_up - t_build,
    }  # fmt: skip


def create_table(ds, controller_url: str, replication: int) -> None:
    from pinot_tpu.cluster.http import RemoteControllerClient
    from pinot_tpu.common import TableConfig

    rc = RemoteControllerClient(controller_url)
    rc.add_schema(program_schema(ds))
    rc.add_table(TableConfig(ds.TABLE, replication=replication))
