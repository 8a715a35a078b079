"""Broker routing: segment pruning + replica instance selection.

Reference parity: BrokerRoutingManager (pinot-broker/.../routing/
BrokerRoutingManager.java:101); instance selectors BalancedInstanceSelector /
ReplicaGroupInstanceSelector / StrictReplicaGroupInstanceSelector
(pinot-broker/.../routing/instanceselector/); AdaptiveServerSelector
(routing/adaptiveserverselector/ — latency-aware replica ranking); the
pruners — ColumnValueSegmentPruner (min/max interval tests),
TimeSegmentPruner, MultiPartitionColumnsSegmentPruner (partition membership
on EQ/IN predicates) — operating here on controller-stored per-segment
stats/partition metadata instead of on-disk metadata; and the
TimeBoundaryManager for hybrid offline+realtime tables
(broker/routing/timeboundary/).
"""

from __future__ import annotations

import itertools
import threading
import zlib

from pinot_tpu.query import ast
from pinot_tpu.query.ast import CompareOp


def _interval(stats: dict, col: str):
    s = stats.get(col)
    if s is None:
        return None
    mn, mx = s.get("min"), s.get("max")
    if mn is None or mx is None:
        return None
    if isinstance(mn, dict) or isinstance(mx, dict):  # bytes columns: skip
        return None
    return mn, mx


def _cmp_overlap(op: CompareOp, lo, hi, v) -> bool:
    try:
        if op == CompareOp.EQ:
            return lo <= v <= hi
        if op == CompareOp.NEQ:
            return True  # only prunable when lo==hi==v; keep conservative
        if op == CompareOp.LT:
            return lo < v
        if op == CompareOp.LTE:
            return lo <= v
        if op == CompareOp.GT:
            return hi > v
        if op == CompareOp.GTE:
            return hi >= v
    except TypeError:
        return True
    return True


def segment_can_match(f: ast.FilterExpr | None, stats: dict) -> bool:
    """Conservative test: False only when the filter PROVABLY matches no doc
    of the segment given column [min,max] stats."""
    if f is None:
        return True
    if isinstance(f, ast.And):
        return all(segment_can_match(c, stats) for c in f.children)
    if isinstance(f, ast.Or):
        return any(segment_can_match(c, stats) for c in f.children)
    if isinstance(f, ast.Compare):
        left, op, right = f.left, f.op, f.right
        if isinstance(left, ast.Literal) and isinstance(right, ast.Identifier):
            from pinot_tpu.query.plan import _FLIP

            left, right, op = right, left, _FLIP[op]
        if isinstance(left, ast.Identifier) and isinstance(right, ast.Literal):
            iv = _interval(stats, left.name)
            if iv is not None:
                v = right.value
                if isinstance(v, str) != isinstance(iv[0], str):
                    return True
                return _cmp_overlap(op, iv[0], iv[1], v)
        return True
    if isinstance(f, ast.Between) and isinstance(f.expr, ast.Identifier) and not f.negated:
        if isinstance(f.low, ast.Literal) and isinstance(f.high, ast.Literal):
            iv = _interval(stats, f.expr.name)
            if iv is not None:
                try:
                    return not (f.high.value < iv[0] or f.low.value > iv[1])
                except TypeError:
                    return True
        return True
    if isinstance(f, ast.In) and isinstance(f.expr, ast.Identifier) and not f.negated:
        iv = _interval(stats, f.expr.name)
        if iv is not None:
            try:
                return any(
                    iv[0] <= v.value <= iv[1] for v in f.values if isinstance(v, ast.Literal)
                )
            except TypeError:
                return True
        return True
    # NOT / LIKE / REGEXP / IsNull: never prune
    return True


#: the states in which a replica answers queries
SERVING_STATES = ("ONLINE", "CONSUMING")


def _serving_replicas(ideal_state: dict[str, dict[str, str]], seg: str) -> list[str]:
    """The servers that may be asked for `seg`, in a fixed order."""
    return sorted(s for s, st in ideal_state.get(seg, {}).items() if st in SERVING_STATES)


class BalancedInstanceSelector:
    """Round-robin replica choice (BalancedInstanceSelector parity; the
    adaptive latency-aware variant plugs in here later): a request id that
    steps once a query, plus the segment's index in the query, picks the
    replica. One counter stepped once a *segment* picked the same replica of
    every segment in every query where replicas lie in regular pairs and the
    segments are even in number: half of the servers never served."""

    def __init__(self):
        self._rr = itertools.count()

    def select(
        self, ideal_state: dict[str, dict[str, str]], segments: list[str]
    ) -> tuple[dict[str, list[str]], list[str]]:
        """segment list -> ({server_id: [segments]}, unroutable_segments),
        picking one ONLINE replica per segment. Callers must surface
        unroutable segments as an error, never as silently-missing rows."""
        request_id = next(self._rr)
        plan: dict[str, list[str]] = {}
        unroutable: list[str] = []
        for i, seg in enumerate(segments):
            replicas = _serving_replicas(ideal_state, seg)
            if not replicas:
                unroutable.append(seg)
                continue
            plan.setdefault(replicas[(request_id + i) % len(replicas)], []).append(seg)
        return plan, unroutable


class ReplicaGroupInstanceSelector:
    """Route each query to ONE replica index across all segments
    (ReplicaGroupInstanceSelector parity): minimal fan-out when replicas are
    placed as complete copies. Segments missing from the chosen replica fall
    through to any other ONLINE replica (non-strict)."""

    def __init__(self, strict: bool = False):
        self._rr = itertools.count()
        self.strict = strict

    def select(self, ideal_state, segments):
        group = next(self._rr)
        plan: dict[str, list[str]] = {}
        unroutable: list[str] = []
        for seg in segments:
            replicas = _serving_replicas(ideal_state, seg)
            if not replicas:
                unroutable.append(seg)
                continue
            pick = replicas[group % len(replicas)]
            plan.setdefault(pick, []).append(seg)
        if self.strict and len(plan) > 1:
            # StrictReplicaGroup: every segment must come from the same
            # group index; mixed placement means the grouping is broken
            counts = {s: len(v) for s, v in plan.items()}
            raise RuntimeError(f"strict replica-group routing failed: segments span servers {counts}")
        return plan, unroutable


class AdaptiveServerSelector:
    """Latency-aware replica choice (AdaptiveServerSelector parity, the
    LATENCY strategy): EWMA of observed per-server latency; each segment goes
    to its lowest-score ONLINE replica. Brokers call `record()` after every
    scatter; unobserved servers score 0 (get traffic to gather data)."""

    def __init__(self, alpha: float = 0.3):
        self.alpha = alpha
        self._ewma: dict[str, float] = {}
        self._lock = threading.Lock()

    def record(self, server_id: str, latency_ms: float) -> None:
        with self._lock:
            cur = self._ewma.get(server_id)
            self._ewma[server_id] = (
                latency_ms if cur is None else self.alpha * latency_ms + (1 - self.alpha) * cur
            )

    def score(self, server_id: str) -> float:
        with self._lock:
            return self._ewma.get(server_id, 0.0)

    def select(self, ideal_state, segments):
        plan: dict[str, list[str]] = {}
        unroutable: list[str] = []
        for seg in segments:
            replicas = _serving_replicas(ideal_state, seg)
            if not replicas:
                unroutable.append(seg)
                continue
            pick = min(replicas, key=lambda s: (self.score(s), s))
            plan.setdefault(pick, []).append(seg)
        return plan, unroutable


# -- partition pruning (MultiPartitionColumnsSegmentPruner parity) -----------


def partition_of(value, num_partitions: int) -> int:
    """Stable partition function (Murmur-role; crc32 for strings, modulo for
    ints — matches the builder side writing segment partition metadata)."""
    if isinstance(value, (int, float)) and not isinstance(value, bool):
        return int(value) % num_partitions
    return zlib.crc32(str(value).encode()) % num_partitions


def segment_partitions_match(f: ast.FilterExpr | None, partitions: dict) -> bool:
    """False only when every EQ/IN value on a partitioned column hashes
    outside this segment's partition set."""
    if not partitions or f is None:
        return True
    if isinstance(f, ast.And):
        return all(segment_partitions_match(c, partitions) for c in f.children)
    if isinstance(f, ast.Or):
        return any(segment_partitions_match(c, partitions) for c in f.children)
    if isinstance(f, ast.Compare) and f.op == CompareOp.EQ:
        left, right = f.left, f.right
        if isinstance(left, ast.Literal) and isinstance(right, ast.Identifier):
            left, right = right, left
        if isinstance(left, ast.Identifier) and isinstance(right, ast.Literal):
            p = partitions.get(left.name)
            if p:
                return partition_of(right.value, p["numPartitions"]) in set(p["partitionIds"])
        return True
    if isinstance(f, ast.In) and isinstance(f.expr, ast.Identifier) and not f.negated:
        p = partitions.get(f.expr.name)
        if p:
            ids = set(p["partitionIds"])
            return any(
                partition_of(v.value, p["numPartitions"]) in ids
                for v in f.values
                if isinstance(v, ast.Literal)
            )
        return True
    return True


# -- time boundary (hybrid offline+realtime routing) -------------------------


class TimeBoundary:
    """Hybrid-table split (TimeBoundaryManager parity): offline serves
    time <= boundary, realtime serves time > boundary, where boundary is the
    max time value committed to the offline table."""

    def __init__(self, time_column: str, boundary):
        self.time_column = time_column
        self.boundary = boundary

    @staticmethod
    def compute(offline_meta: dict[str, dict], time_column: str) -> "TimeBoundary | None":
        hi = None
        for m in offline_meta.values():
            s = (m.get("stats") or {}).get(time_column)
            if s and isinstance(s.get("max"), (int, float)):
                hi = s["max"] if hi is None else max(hi, s["max"])
        return TimeBoundary(time_column, hi) if hi is not None else None

    def offline_sql(self, sql: str) -> str:
        return _with_time_predicate(sql, f"{self.time_column} <= {self.boundary}")

    def realtime_sql(self, sql: str) -> str:
        return _with_time_predicate(sql, f"{self.time_column} > {self.boundary}")


def _search_outside_quotes(pattern: str, sql: str, start: int = 0):
    """re.search that ignores matches inside single-quoted SQL string
    literals ('' is the escaped quote) — 'WHERE msg = ''over the limit'''
    must not split at the LIMIT inside the literal."""
    import re

    masked = list(sql)
    in_str = False
    for i, ch in enumerate(sql):
        if ch == "'":
            in_str = not in_str  # '' escape toggles twice: net unchanged
        elif in_str:
            masked[i] = "\0"
    return re.search(pattern, "".join(masked[start:]), re.IGNORECASE)


def _with_time_predicate(sql: str, predicate: str) -> str:
    """Inject an AND predicate into the (single-table, v1) query text — the
    string-level analog of attaching the time filter to BrokerRequest."""
    _TAIL = r"\b(GROUP\s+BY|ORDER\s+BY|LIMIT|HAVING)\b"
    m = _search_outside_quotes(r"\bWHERE\b", sql)
    if m:
        # Parenthesize the ORIGINAL predicate too: 'a=1 OR b=2' must become
        # '(boundary) AND (a=1 OR b=2)', otherwise AND binds tighter than OR
        # and the boundary no longer constrains the OR branch (rows in the
        # offline/realtime overlap window would be returned by BOTH legs).
        tail = _search_outside_quotes(_TAIL, sql, m.end())
        end = m.end() + (tail.start() if tail else len(sql) - m.end())
        rest = sql[m.end() : end].strip()
        tail_str = sql[end:].strip()
        out = sql[: m.end()] + f" ({predicate}) AND ({rest})"
        return out + (" " + tail_str if tail_str else "")
    tail = _search_outside_quotes(_TAIL, sql)
    pos = tail.start() if tail else len(sql)
    return sql[:pos].rstrip() + f" WHERE {predicate} " + sql[pos:]


# -- the route snapshot --------------------------------------------------------


def _routable(ideal: dict[str, dict[str, str]], view: dict[str, dict[str, str]] | None) -> dict[str, dict[str, str]]:
    """The ideal state less the replicas the external view does not confirm."""
    if view is None:
        return ideal
    return {
        seg: {s: st for s, st in replicas.items() if view.get(seg, {}).get(s) in SERVING_STATES}
        for seg, replicas in ideal.items()
    }


class RouteSnapshot:
    """Everything the broker reads of the controller to route a query on one
    logical table, as of one `token`: the configs of the table and of its
    `_REALTIME` twin (None where there is none), the schema, every segment's
    metadata, the ideal state and the external view of each physical table,
    and the server handles. A replica is routed to where both say it serves:
    the ideal state is what the controller wants, the external view what the
    servers have confirmed (`routable`; a table whose transitions are
    synchronous calls has no view of its own, and the ideal state stands for
    it). `Controller.route_snapshot` builds it and
    `RemoteControllerClient.route_snapshot` rebuilds it from `to_doc`; the
    broker holds one a table and asks once a query whether its token still
    stands (upstream's broker likewise routes from a routing table kept in
    memory and rebuilt when the external view changes). Read-only once built:
    queries on other threads route from the same object."""

    def __init__(self, table, token, offline_cfg, rt_cfg, schema, meta, ideal, servers, instances, external=None):
        self.table = table
        self.token = token
        self.offline_cfg = offline_cfg
        self.rt_cfg = rt_cfg
        self.schema = schema
        #: physical table -> {segment: metadata}; physical table -> ideal state
        self.meta: dict[str, dict[str, dict]] = meta
        self.ideal: dict[str, dict[str, dict[str, str]]] = ideal
        #: physical table -> external view; None where none is kept for the table
        self.external: dict[str, dict[str, dict[str, str]] | None] = external or {}
        #: physical table -> the replicas a query may be sent to, in the ideal state's shape
        self.routable = {t: _routable(i, self.external.get(t)) for t, i in ideal.items()}
        #: server id -> handle (in-process object or RemoteServerClient)
        self.servers: dict[str, object] = servers
        #: server id -> instance document (what `to_doc` ships of the servers)
        self.instances: dict[str, dict] = instances
        rt_name = f"{table}_REALTIME"
        #: hybrid split, TimeBoundaryManager parity: offline <= boundary < realtime
        self.time_boundary: TimeBoundary | None = None
        if offline_cfg is not None and rt_cfg is not None and offline_cfg.time_column:
            self.time_boundary = TimeBoundary.compute(meta.get(table, {}), offline_cfg.time_column)
            self.leg_tables = [rt_name] if self.time_boundary is None else [table, rt_name]
        elif offline_cfg is not None:
            self.leg_tables = [table]
        else:
            self.leg_tables = [rt_name]
        #: the metadata of every segment a query's legs can touch, and its rows
        self.all_meta: dict[str, dict] = {}
        for t in self.leg_tables:
            self.all_meta.update(meta.get(t, {}))
        self.total_docs = sum(m.get("numDocs", 0) for m in self.all_meta.values())

    @property
    def exists(self) -> bool:
        return self.offline_cfg is not None or self.rt_cfg is not None

    def legs(self, sql: str) -> list[tuple[str, str]]:
        """(physical table, sql text) of each leg of a query."""
        if len(self.leg_tables) == 2:
            return [
                (self.leg_tables[0], self.time_boundary.offline_sql(sql)),
                (self.leg_tables[1], self.time_boundary.realtime_sql(sql)),
            ]
        return [(self.leg_tables[0], sql)]

    def has_consuming(self) -> bool:
        """Some segment of an ideal state has no committed metadata yet: it
        is consuming, and its rows advance with no write the token sees."""
        return any(s not in self.meta.get(t, {}) for t, ideal in self.ideal.items() for s in ideal)

    def to_doc(self) -> dict:
        """The JSON form (`GET /tables/{t}/route`): handles stay behind, the
        instance documents say where the servers listen."""
        return {
            "table": self.table,
            "token": self.token,
            "offlineConfig": self.offline_cfg.to_json() if self.offline_cfg is not None else None,
            "realtimeConfig": self.rt_cfg.to_json() if self.rt_cfg is not None else None,
            "schema": self.schema.to_json() if self.schema is not None else None,
            "segments": self.meta,
            "idealStates": self.ideal,
            "externalViews": self.external,
            "instances": self.instances,
        }

    @classmethod
    def from_doc(cls, doc: dict, make_handle) -> "RouteSnapshot":
        """Rebuild from `to_doc`; `make_handle(instance document)` gives the
        handle of each server that listens on a port."""
        from pinot_tpu.common.config import TableConfig
        from pinot_tpu.common.types import Schema

        def config(key):
            return TableConfig.from_json(doc[key]) if doc.get(key) else None

        instances = doc.get("instances") or {}
        return cls(
            doc["table"],
            doc["token"],
            config("offlineConfig"),
            config("realtimeConfig"),
            Schema.from_json(doc["schema"]) if doc.get("schema") else None,
            doc.get("segments") or {},
            doc.get("idealStates") or {},
            {sid: make_handle(d) for sid, d in instances.items() if d and d.get("port")},
            instances,
            doc.get("externalViews"),
        )
