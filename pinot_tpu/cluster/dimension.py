"""Dimension tables: fully-in-memory PK-keyed lookup tables + LOOKUP UDF.

Reference parity: DimensionTableDataManager (pinot-core/.../data/manager/
offline/DimensionTableDataManager.java) — a table flagged dimTable is loaded
entirely into a primary-key map on every server, powering the lookUp() UDF
(LookupTransformFunction): lookUp('dimTable', 'destColumn', 'pkCol', pkExpr,
...).

A server owns the dimension tables it hosts (`DimensionRegistry`, state of
`cluster.server.Server`): it rebuilds a table's manager from the segments it
hosts whenever one of them is loaded or dropped. The table is columns: the
primary keys sorted once at load, every attribute a dictionary and one code a
key, so a lookup of an array of keys is one `np.searchsorted` (a subtraction
for a dense integer key) and a take. The host evaluator (`query/host_exec.py`)
and the device lowering (`query/plan.py`) both read the registry the server
has put in scope (`serving`); the device gathers through a resident
code -> codes operand that `operand` builds once a (foreign-key dictionary,
table generation), every attribute's code a bit field of one word.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading
import weakref

import numpy as np


def _key_array(values) -> np.ndarray:
    """Key values as an array np.searchsorted can order: strings as `<U`, numbers as they are."""
    a = np.asarray(values)
    return a.astype(str) if a.dtype.kind in "OSU" else a


def _positions(uniq: np.ndarray, probe) -> np.ndarray:
    """Where each of `probe` sits in the sorted distinct `uniq`; -1 where it does not
    (or where a string is probed among numbers, a number among strings)."""
    p = _key_array(probe)
    if len(uniq) == 0 or (p.dtype.kind == "U") != (uniq.dtype.kind == "U"):
        return np.full(len(p), -1, dtype=np.int64)
    i = np.minimum(np.searchsorted(uniq, p), len(uniq) - 1)
    return np.where(uniq[i] == p, i, -1)


class DimensionTableDataManager:
    def __init__(self, table: str, pk_columns: list[str], schema=None, generation: int = 0):
        if not pk_columns:
            raise ValueError(f"dimension table {table!r} needs primaryKeyColumns in its schema")
        self.table = table
        self.pk_columns = list(pk_columns)
        #: which build of the table this is, within its registry: a reloaded
        #: table is a new object of a higher generation
        self.generation = generation
        # schema-declared string columns: authoritative even before any
        # segment loads (an all-miss lookup must already return 'null'
        # strings, not NaNs). Segment loads add to this set as a fallback
        # when no schema was provided.
        self._schema_str_cols: frozenset[str] = frozenset(
            c for c, f in schema.fields.items() if f.data_type.np_dtype == np.dtype(object)
        ) if schema is not None else frozenset()
        self._str_cols: set[str] = set(self._schema_str_cols)
        self._lock = threading.Lock()
        self._set_keys([np.zeros(0, dtype=np.int64) for _ in self.pk_columns], {})
        # foreign-key dictionary -> {"pos": its values' key positions, "words": {word: operand}};
        # an entry goes with its dictionary (a dropped segment), all of them with this object (a reload)
        self._operands: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

    # -- load ------------------------------------------------------------------

    def _set_keys(self, key_cols: list[np.ndarray], attrs: dict) -> None:
        """Keys in their sorted order, later rows winning a repeated key, and every
        attribute as (sorted distinct values of the kept rows, a code a key)."""
        n = len(key_cols[0])
        uniqs, coded = [], []
        for k in key_cols:
            u, inv = np.unique(k, return_inverse=True)
            uniqs.append(u)
            coded.append(inv.astype(np.int64))
        combo = coded[0] if n else np.zeros(0, dtype=np.int64)
        for u, c in zip(uniqs[1:], coded[1:]):
            if len(u) and int(combo.max(initial=0)) >= (1 << 62) // max(len(u), 1):
                raise ValueError(f"dimension table {self.table!r}: composite key space exceeds int64")
            combo = combo * len(u) + c
        order = np.argsort(combo, kind="stable")
        sk = combo[order]
        last = np.ones(len(sk), dtype=bool)
        last[:-1] = sk[1:] != sk[:-1]
        rows = order[last]  # of a repeated key the last row: later segments win
        self._uniqs = uniqs
        self._combos = sk[last]
        single = len(key_cols) == 1
        keys = uniqs[0] if single else None  # a single key's combos are 0..n-1 over its own distinct values
        # a dense integer key (1..n, as a star schema's surrogate keys are) is looked up by subtraction
        self._dense_lo = (
            int(keys[0])
            if single and len(keys) and keys.dtype.kind in "iu" and int(keys[-1]) - int(keys[0]) == len(keys) - 1
            else None
        )
        dest = {}
        for c, (values, codes) in attrs.items():
            codes = codes[rows]
            present = np.bincount(codes, minlength=len(values)) > 0
            dest[c] = (values[present], (np.cumsum(present) - 1)[codes].astype(np.int32))
        self._dest: dict[str, tuple[np.ndarray, np.ndarray]] = dest
        # Where each attribute's code sits in the operand a launch gathers through: (word, shift, mask). A
        # field holds codes 0..cardinality (the last for a key without a row); the narrowest attributes share
        # the first 31-bit word, so that one gather a foreign key serves every attribute a query reads of it
        fields, used = {}, []
        for bits, c in sorted((len(values).bit_length(), c) for c, (values, _) in dest.items()):
            word = next((w for w, u in enumerate(used) if u + bits <= 31), len(used))
            if word == len(used):
                used.append(0)
            fields[c] = (word, used[word], (1 << bits) - 1)
            used[word] += bits
        self._fields: dict[str, tuple[int, int, int]] = fields

    def load_segments(self, segments) -> None:
        """Full rebuild from the table's current segments (the reference
        reloads the whole map on segment changes too)."""
        segments = list(segments)
        str_cols: set[str] = set()
        per_col: dict[str, list] = {}
        for seg in segments:
            for c, ci in seg.columns.items():
                if getattr(ci, "is_mv", False):
                    continue  # a multi-value attribute is none a lookUp returns
                dictionary = getattr(ci, "dictionary", None)
                if dictionary is not None:  # the loaded segment's own dictionary and codes
                    values, codes = _key_array(dictionary.values), np.asarray(ci.forward)
                else:
                    values, codes = np.unique(_key_array(ci.materialize()), return_inverse=True)
                dt = getattr(ci, "data_type", None)
                if (dt.np_dtype == np.dtype(object)) if dt is not None else values.dtype.kind == "U":
                    str_cols.add(c)
                per_col.setdefault(c, []).append((values, codes))
        attrs = {}
        for c, parts in per_col.items():
            if len(parts) != len(segments):
                continue  # not a column of every segment
            if len(parts) == 1:
                attrs[c] = parts[0]
                continue
            union = np.unique(np.concatenate([v for v, _ in parts]))
            attrs[c] = (union, np.concatenate([np.searchsorted(union, v)[codes] for v, codes in parts]))
        missing = [c for c in self.pk_columns if c not in attrs] if segments else []
        if missing:
            raise ValueError(f"dimension table {self.table!r}: its segments lack primary key columns {missing}")
        key_cols = [attrs[c][0][attrs[c][1]] for c in self.pk_columns] if segments else [np.zeros(0, dtype=np.int64) for _ in self.pk_columns]
        with self._lock:
            self._set_keys(key_cols, attrs)
            self._operands = weakref.WeakKeyDictionary()
            # full rebuild: schema-declared string columns plus what THIS
            # segment set shows (stale dtype observations don't survive)
            self._str_cols = set(self._schema_str_cols) | str_cols

    # -- lookup ----------------------------------------------------------------

    def positions(self, key_arrays) -> np.ndarray:
        """The place among the table's keys of each probed key, one array a
        primary-key column in their order; -1 where the table has no such row."""
        if len(key_arrays) != len(self.pk_columns):
            raise ValueError(f"dimension table {self.table!r} is keyed by {self.pk_columns}, not by {len(key_arrays)} values")
        if self._dense_lo is not None:
            p = np.asarray(key_arrays[0])
            if p.dtype.kind in "iu":
                pos = p.astype(np.int64) - self._dense_lo
                return np.where((pos >= 0) & (pos < len(self._combos)), pos, -1)
        combo, hit = None, None
        for u, probe in zip(self._uniqs, key_arrays):
            c = _positions(u, probe)
            hit = (c >= 0) if hit is None else hit & (c >= 0)
            combo = c if combo is None else combo * len(u) + c
        if len(self._uniqs) > 1:  # a single key's codes are its positions already
            pos = _positions(self._combos, np.where(hit, combo, 0))
            return np.where(hit, pos, -1)
        return combo

    def has_column(self, column: str) -> bool:
        return column in self._dest

    def dest_values(self, dest_column: str) -> np.ndarray:
        """The destination's distinct values over the table's rows, ascending: what a lookup's codes index."""
        return self._dest[dest_column][0]

    def _codes_at(self, dest_column: str, pos: np.ndarray) -> np.ndarray:
        """The destination's code at each key position, its cardinality (one past the last code) where the position is -1."""
        values, codes = self._dest[dest_column]
        if not len(codes):
            return np.full(len(pos), len(values), dtype=np.int32)
        return np.where(pos >= 0, codes[np.maximum(pos, 0)], len(values)).astype(np.int32)

    def lookup_codes(self, dest_column: str, key_arrays) -> np.ndarray:
        """Codes into `dest_values`, the cardinality (one past the last) where a key has no row."""
        return self._codes_at(dest_column, self.positions(key_arrays))

    def lookup(self, pk: tuple):
        """One key's row as a dict, or None."""
        with self._lock:
            pos = int(self.positions([np.asarray([v]) for v in pk])[0])
            if pos < 0:
                return None
            return {c: values[codes[pos]].item() for c, (values, codes) in self._dest.items()}

    def decode_table(self, dest_column: str) -> np.ndarray:
        """What each code of `lookup_codes` stands for: the destination's
        distinct values and, last, the null substitute of its type ('null'
        for a string column, as an object array; NaN for a numeric one, as
        float64 — FieldSpec default-null parity). String-ness comes from the
        dim table's SCHEMA, so a column without a single row still answers
        'null' strings."""
        is_str = dest_column in self._str_cols
        values = self._dest[dest_column][0] if dest_column in self._dest else np.zeros(0)
        if is_str:
            return np.append(values.astype(object), "null")
        return np.append(values.astype(np.float64), np.nan)

    def lookup_column(self, dest_column: str, keys) -> np.ndarray:
        """The destination's value for each key; `keys` is one array a
        primary-key column (or a list of key tuples). A key without a row
        takes the null substitute (`decode_table`), so an all-miss batch on a
        string column returns 'null' strings and not NaNs."""
        if isinstance(keys, list) and (not keys or isinstance(keys[0], tuple)):
            keys = [np.asarray(c) for c in zip(*keys)] or [np.zeros(0) for _ in self.pk_columns]
        with self._lock:
            table = self.decode_table(dest_column)
            if dest_column not in self._dest:  # a column the table lacks: every row the null substitute
                return table[np.zeros(len(np.asarray(keys[0])), dtype=np.int64)]
            return table[self.lookup_codes(dest_column, keys)]

    def field(self, dest_column: str) -> tuple[int, int, int]:
        """(which operand word of a foreign key, shift, mask) holds the destination's code."""
        return self._fields[dest_column]

    def operand(self, fk_dictionary, word: int) -> tuple[np.ndarray, bool]:
        """(the operand a launch gathers a foreign key's codes through: code ->
        the codes of every destination whose field lies in `word`, each at its
        shift, the destination's cardinality for a key without a row and for
        the padding to a power of two; whether this call built it). Built
        once a (foreign-key dictionary, word) of this generation and kept
        until the dictionary's segment or this table is dropped, so the
        caller can keep its copy on the chip as long."""
        with self._lock:
            ent = self._operands.get(fk_dictionary)
            if ent is None:
                ent = self._operands[fk_dictionary] = {"pos": self.positions([fk_dictionary.values]), "words": {}}
            op = ent["words"].get(word)
            if op is not None:
                return op, False
            pos = ent["pos"]
            op = np.zeros(1 << max(len(pos) - 1, 0).bit_length(), dtype=np.int32)
            for dest, (w, shift, _) in self._fields.items():
                if w == word:
                    op |= len(self._dest[dest][0]) << shift
                    op[: len(pos)] ^= (self._codes_at(dest, pos) ^ len(self._dest[dest][0])) << shift
            ent["words"][word] = op
            return op, True

    # -- sizes -----------------------------------------------------------------

    @property
    def size(self) -> int:
        with self._lock:
            return len(self._combos)

    def resident_bytes(self) -> int:
        with self._lock:
            return int(
                sum(u.nbytes for u in self._uniqs) + self._combos.nbytes
                + sum(v.nbytes + c.nbytes for v, c in self._dest.values())
            )  # fmt: skip

    def operand_bytes(self) -> int:
        with self._lock:
            return int(sum(op.nbytes for ent in self._operands.values() for op in ent["words"].values()))


class DimensionRegistry:
    """The dimension tables one server hosts, by name."""

    def __init__(self):
        self._tables: dict[str, DimensionTableDataManager] = {}
        self._lock = threading.Lock()
        self._generations = itertools.count(1)

    def rebuild(self, table: str, pk_columns: list[str], segments, schema=None) -> DimensionTableDataManager:
        """A new generation of `table` from `segments`, in their order (a later
        one wins a repeated key); the old one and its operands are dropped."""
        mgr = DimensionTableDataManager(table, pk_columns, schema=schema, generation=next(self._generations))
        mgr.load_segments(segments)
        with self._lock:
            self._tables[table] = mgr
        return mgr

    def drop(self, table: str) -> None:
        with self._lock:
            self._tables.pop(table, None)

    def get(self, table: str) -> DimensionTableDataManager:
        with self._lock:
            m = self._tables.get(table)
        if m is None:
            raise KeyError(
                f"no dimension table {table!r} loaded (set extra.isDimTable=true on its table config)"
            )
        return m

    def tables(self) -> dict[str, DimensionTableDataManager]:
        with self._lock:
            return dict(self._tables)

    def resident_bytes(self) -> tuple[int, int]:
        """(bytes of the tables' columns on the host, bytes of their lookup operands, each with a copy on the chip once used)."""
        tables = self.tables().values()
        return sum(t.resident_bytes() for t in tables), sum(t.operand_bytes() for t in tables)

    @contextlib.contextmanager
    def serving(self):
        """This registry is the one `get_dim_table` reads, for the extent of a server's work on a query."""
        token = _serving.set(self)
        try:
            yield self
        finally:
            _serving.reset(token)


_serving: contextvars.ContextVar[DimensionRegistry | None] = contextvars.ContextVar("pinot_dim_tables", default=None)


def get_dim_table(table: str) -> DimensionTableDataManager:
    """The named dimension table of the server whose query this is."""
    registry = _serving.get()
    if registry is None:
        raise KeyError(
            f"no dimension table {table!r} loaded (set extra.isDimTable=true on its table config)"
        )
    return registry.get(table)
