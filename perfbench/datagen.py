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
import json
import shutil
import tarfile
import time
from pathlib import Path

import numpy as np

from perfbench import tables


#: one attempt with room. The controller answers an upload once the server has loaded the segment: 25-27 s with
#: five in flight, 39 s with six (PERF.md, PR 26). The client's own 30 s and three attempts sent a segment again
#: while its first upload was still being worked on, and the controller assigns a segment anew each time it arrives.
#: An upload that ends in an error raises here; `run.py` builds and sends that segment once more, after the others
UPLOAD_TIMEOUT_S = 300.0


def dataset_module(name: str):
    return importlib.import_module(f"perfbench.datasets.{name}")


def program_schema(ds, table: dict | None = None):
    """The program's `Schema` of a table: the generator's columns by their roles, and what the
    declaration's `schema` adds (`primaryKeyColumns`; a column named under `dateTimeFieldSpecs`
    is a DATE_TIME field with that entry's `format` and `granularity`). `table` is an entry of
    `tables.declared`; None, the dataset module's own table with nothing declared."""
    from pinot_tpu.common import DataType, Schema
    from pinot_tpu.common.types import FieldSpec, FieldType

    table = table or tables.own(ds)
    rows, extras = tables.generator(ds, table["generator"])["schema"], table["schema"]
    date_times = {f["name"]: f for f in extras.get("dateTimeFieldSpecs", [])}
    schema = Schema.build(
        table["name"],
        dimensions=[(c, DataType[t]) for c, t, role in rows if role == "dimension" and c not in date_times],
        metrics=[(c, DataType[t]) for c, t, role in rows if role == "metric" and c not in date_times],
        primary_key_columns=extras.get("primaryKeyColumns", []),
    )
    for c, t, _ in rows:
        if c in date_times:
            schema.add(FieldSpec(c, DataType[t], FieldType.DATE_TIME, format=date_times[c].get("format"), granularity=date_times[c].get("granularity")))
    return schema


def _unread(declared, sent, path: str) -> list[str]:
    """The paths of `declared` that `sent`, the program's own rendering of what it read, lacks or holds otherwise."""
    if isinstance(declared, dict) and isinstance(sent, dict):
        return [p for k, v in declared.items() for p in ([f"{path}{k}"] if k not in sent else _unread(v, sent[k], f"{path}{k}."))]
    if isinstance(declared, list) and isinstance(sent, list) and len(declared) == len(sent):
        return [p for i, (d, s) in enumerate(zip(declared, sent)) for p in _unread(d, s, f"{path}{i}.")]
    return [] if declared == sent else [path.rstrip(".")]


def table_config(table: dict):
    """The `TableConfig` the controller is sent: the declaration's `tableConfig` (empty where a
    configuration declares none: the program's defaults) read by the program's own
    `TableConfig.from_json` under the entry's name and replication; a key that reading drops or
    changes is none the program knows, and ends set-up by its name."""
    from pinot_tpu.common import TableConfig

    declared = table["tableConfig"]
    for k in ("tableName", "replication"):
        if k in declared:
            raise ValueError(f"table {table['name']!r}: tableConfig states {k!r}, which the entry's own `name` and `replication` say")
    config = TableConfig.from_json(json.dumps({**declared, "tableName": table["name"], "replication": table["replication"]}))
    unread = _unread(declared, json.loads(config.to_json()), "")
    if unread:
        raise ValueError(f"table {table['name']!r}: the program's TableConfig.from_json does not read tableConfig keys {unread}")
    return config


def assemble(ds, cols: dict, name: str, table: dict | None = None):
    """(segment without its indexes, the program's builder of the table). Encodings by the
    builder's own rule (`SegmentBuilder._use_dictionary`): by default dimensions dictionary-
    encoded over the values present in this segment and metrics raw, a declared
    `noDictionaryColumns` / `dictionaryColumns` the other way, a STRING always coded."""
    from pinot_tpu.common import DataType
    from pinot_tpu.segment.builder import SegmentBuilder
    from pinot_tpu.segment.dictionary import Dictionary
    from pinot_tpu.segment.segment import ColumnIndex, ImmutableSegment
    from pinot_tpu.segment.stats import ColumnStats

    table = table or tables.own(ds)
    schema = program_schema(ds, table)
    builder = SegmentBuilder(schema, table_config(table))
    n = len(next(iter(cols.values())).codes)
    seg = ImmutableSegment(name=name, schema=schema, n_docs=n)
    for col in schema.columns:  # the schema's order: dimensions, then metrics
        dt = schema[col].data_type
        codes, vocab = cols[col]
        if not builder._use_dictionary(col):
            vals = np.ascontiguousarray(codes if vocab is None else vocab[codes], dtype=dt.np_dtype)
            stats = ColumnStats.collect(col, dt, vals, len(np.unique(vals)))
            seg.columns[col] = ColumnIndex(col, dt, None, vals, stats)
            continue
        if vocab is None:  # raw values (a key, a metric declared coded): the dictionary is their own values
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
    return seg, builder


def add_indexes(seg, builder) -> None:
    """The table config's indexes by the program's own builders, as `SegmentBuilder.build` runs them
    after the columns: a star table a `starTreeConfigs` entry, then bloom, inverted, range and the rest.
    A table config that declares none leaves `seg.extras` empty."""
    star_trees = builder.config.indexing.star_tree_configs
    if star_trees:  # the import is pandas: not for a table that declares no star-tree
        from pinot_tpu.segment.startree import build_star_table

        seg.extras["startree"] = [build_star_table(seg, st_cfg) for st_cfg in star_trees]
    builder._build_aux_indexes(seg)


def build_segment(ds, cols: dict, name: str, table: dict | None = None):
    """An ImmutableSegment of generated columns as `SegmentBuilder(schema, table config).build`
    makes it of the same rows (`tests/test_datagen.py`); `table` is an entry of `tables.declared`,
    None the dataset module's own table with the program's default encodings and no index."""
    seg, builder = assemble(ds, cols, name, table)
    add_indexes(seg, builder)
    return seg


def build_and_upload(job: dict) -> dict:
    """One segment: generate, assemble, index, write as .ptseg, push to the controller."""
    from pinot_tpu.cluster.http import RemoteControllerClient
    from pinot_tpu.segment.builder import write_segment

    t0 = time.perf_counter()
    ds = dataset_module(job["dataset"])
    table = job["table"]
    name = table["name"]
    cols = tables.generator(ds, table["generator"])["segment"](job["seed"], job["index"], job["rows"], job["config"])
    t_gen = time.perf_counter()
    seg, builder = assemble(ds, cols, f"{name}_{job['index']}", table)
    del cols  # a 4M-row, 30-column segment is a gigabyte as generated; the machine has 40 GiB for everything
    t_cols = time.perf_counter()
    add_indexes(seg, builder)
    t_index = time.perf_counter()
    star_records = [st.n_rows for st in seg.extras.get("startree", [])]
    seg_dir = Path(write_segment(seg, job["out_dir"]))
    seg_name = seg.name
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
    controller = RemoteControllerClient(job["controller_url"], timeout=UPLOAD_TIMEOUT_S, max_attempts=1)
    controller._post(f"/segments/{name}", body, "application/gzip")
    shutil.rmtree(seg_dir)
    t_up = time.perf_counter()
    return {
        "table": name, "segment": seg_name, "rows": job["rows"], "fileBytes": nbytes, "starRecords": star_records,
        "gen_s": t_gen - t0, "build_s": (t_cols - t_gen) + (t_build - t_index), "index_s": t_index - t_cols, "upload_s": t_up - t_build,
    }  # fmt: skip


def create_table(ds, controller_url: str, replication: int) -> None:
    """The dataset module's own table, nothing declared: the program's default table config."""
    create_tables(ds, controller_url, [tables.own(ds, replication)])


def create_tables(ds, controller_url: str, declared: list[dict]) -> None:
    """Every table's schema and table config to the controller (`declared`: entries of
    `tables.declared`). Every table config is made before any is sent, so a key the
    program does not know stops set-up before a table exists."""
    from pinot_tpu.cluster.http import RemoteControllerClient

    made = [(program_schema(ds, t), table_config(t)) for t in declared]
    rc = RemoteControllerClient(controller_url)
    for schema, config in made:
        rc.add_schema(schema)
        rc.add_table(config)
