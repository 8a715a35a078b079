"""The tables a configuration declares, as plain data: what set-up loads, in which order.

A configuration without `tables` has one table, the dataset module's `TABLE`,
at the configuration's own `rows`, `segmentRows` and `replication`, with the
program's default table config. One with `tables` says each table of the
deployment: its `name`; the `generator` under which the dataset module makes it
(`TABLES[generator]`, or the module's own `SCHEMA` and `segment` under the name
of its `TABLE`); `rows`, `segmentRows`, `replication` (a number, or
`"everyServer"`) and their `rehearsal` sizes; `schema`, what the program's
`Schema` takes beside the columns (`primaryKeyColumns`, `dateTimeFieldSpecs`);
and `tableConfig`, the JSON the program's own `TableConfig.from_json` reads.

The table the templates query, the *fact* table, is the entry named as the
module's `TABLE`. Its sizes and replication stay the configuration's top-level
keys, which the dataset module, the check and the readers read: its entry may
not state them a second time. It is loaded last.

Nothing here imports the program: the launcher calls `declared` before a role
starts. Whether `tableConfig` holds only what the program reads is for
`datagen.table_config`, which can ask the program.
"""

from __future__ import annotations

from perfbench.cluster import require

ENTRY_KEYS = {"name", "generator", "rows", "segmentRows", "replication", "rehearsal", "schema", "tableConfig"}
SIZE_KEYS = ("rows", "segmentRows", "replication")
SCHEMA_KEYS = {"primaryKeyColumns", "dateTimeFieldSpecs"}
EVERY_SERVER = "everyServer"


def rehearse(config: dict) -> None:
    """The configuration cut to its `rehearsal` sizes, each declared table to its own."""
    config.update(config["rehearsal"])
    for entry in config.get("tables", []):
        entry.update(entry.get("rehearsal", {}))


def generator(ds, name: str) -> dict:
    """`{"schema": [(column, type, role)], "segment": (seed, index, rows, config) -> columns}` of a generator's name."""
    own = {ds.TABLE: {"schema": ds.SCHEMA, "segment": ds.segment}}
    made = {**own, **getattr(ds, "TABLES", {})}
    require(name in made, f"dataset module {ds.__name__} generates no table {name!r}; it generates {sorted(made)}")
    return made[name]


def own(ds, replication: int = 1) -> dict:
    """The dataset module's own table with nothing declared, for a caller that has no configuration (no sizes)."""
    return {"name": ds.TABLE, "generator": ds.TABLE, "replication": replication, "schema": {}, "tableConfig": {}, "fact": True}


def declared(config: dict, ds) -> list[dict]:
    """The deployment's tables, the fact table last, each as
    `{"name", "generator", "rows", "segmentRows", "replication", "schema", "tableConfig", "fact"}`
    with `replication` a number and `tableConfig` empty where none is declared.
    A declaration that cannot be loaded ends the run here, by the table's or the key's name."""
    fact_sizes = {k: config[k] for k in SIZE_KEYS}
    # with nothing declared: the module's own table, the program's default table config
    undeclared = [{"name": ds.TABLE}] if "tables" not in config else []
    out = []
    for entry in undeclared + config.get("tables", []):
        name = entry.get("name")
        require(isinstance(name, str) and name, f"a table of configuration {config['name']} has no name: {entry}")
        where = f"table {name!r} of configuration {config['name']}"
        unknown = sorted(set(entry) - ENTRY_KEYS)
        require(not unknown, f"{where} has keys {unknown} that set-up does not read; it reads {sorted(ENTRY_KEYS)}")
        require(name not in [t["name"] for t in out], f"{where} is declared twice")
        fact = name == ds.TABLE
        if fact:
            stated = [k for k in (*SIZE_KEYS, "rehearsal") if k in entry]
            require(not stated, f"{where} is the table the templates query: its {stated} are the configuration's own top-level keys")
            sizes = dict(fact_sizes)
        else:
            missing = [k for k in SIZE_KEYS if k not in entry]
            require(not missing, f"{where} lacks {missing}")
            sizes = {k: entry[k] for k in SIZE_KEYS}
        gen = generator(ds, entry.get("generator", name))
        rows, seg_rows = sizes["rows"], sizes["segmentRows"]
        require(rows > 0 and rows % seg_rows == 0, f"{where}: rows {rows} not a multiple of segmentRows {seg_rows}")
        if sizes["replication"] == EVERY_SERVER:
            # hosted whole by every server: one segment, kept as often as there are servers
            require(rows == seg_rows, f'{where} is declared "{EVERY_SERVER}" and has {rows // seg_rows} segments, not one')
            sizes["replication"] = int(config["servers"])
        replication = sizes["replication"]
        require(
            isinstance(replication, int) and 1 <= replication <= config["servers"],
            f"{where}: replication {replication!r} is neither \"{EVERY_SERVER}\" nor a number up to the {config['servers']} servers",
        )
        schema = entry.get("schema", {})
        unknown = sorted(set(schema) - SCHEMA_KEYS)
        require(not unknown, f"{where}: schema has keys {unknown}; set-up passes on {sorted(SCHEMA_KEYS)}")
        columns = [c for c, _, _ in gen["schema"]]
        named = list(schema.get("primaryKeyColumns", [])) + [f.get("name") for f in schema.get("dateTimeFieldSpecs", [])]
        strangers = [c for c in named if c not in columns]
        require(not strangers, f"{where}: schema names columns {strangers} that its generator does not make")
        out.append({"name": name, "generator": entry.get("generator", name), **sizes, "schema": schema,
                    "tableConfig": entry.get("tableConfig", {}), "fact": fact})  # fmt: skip
    facts = [t for t in out if t["fact"]]
    require(len(facts) == 1, f"configuration {config['name']} declares tables {[t['name'] for t in out]} and not {ds.TABLE!r}, which its templates query")
    return [t for t in out if not t["fact"]] + facts


def segment_sizes(table: dict) -> list[int]:
    return [table["segmentRows"]] * (table["rows"] // table["segmentRows"])


def segment_names(table: dict) -> list[str]:
    return [f"{table['name']}_{i}" for i in range(table["rows"] // table["segmentRows"])]
