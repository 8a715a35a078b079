"""Request tracing: distributed context propagation, spans, span events,
per-phase timers, and cluster-wide assembly.

Reference parity: pinot-spi/.../trace/Tracing.java (atomic global Tracer
registration, default no-op), InvocationScope spans around operators,
TraceRunnable-style context propagation across combine threads
(pinot-core/.../util/trace/TraceRunnable.java — here via contextvars; the
query scheduler copies the submitting context so segment spans land under
the right parent), and per-phase timers TimerContext/ServerQueryPhase
(ServerQueryExecutorV1Impl.java:161-166).

Distributed model (Dapper-style): the broker mints a W3C-traceparent-shaped
`TraceContext` — always when the `trace=true` query option is set,
probabilistically per ObservabilityConfig.trace_sample_rate otherwise — and
propagates it on every v1 scatter HTTP request (`traceparent` header) and
inside the v2 stage-plan envelope. Each process records its own span
subtree in a local `RequestTrace`; span start times are perf_counter
offsets from the trace-local epoch, and every trace also captures
`anchor_wall_ms` (wall clock at epoch) so the broker can shift remote
subtrees onto its own timeline despite clock skew. Subtrees ship back
piggybacked on the data-path response (v1) or the trailing-EOS stats relay
(v2); `RequestTrace.assemble()` flattens everything into one OTLP-flavored
document served at broker `GET /debug/traces/{requestId}`. Spans carry
`events` for the resilience plane's interesting moments (mailbox send
retries, deadline checkpoints that fired, fault-injector hits, accountant
kills) via the module-level `trace_event()` helper, a no-op when no trace
is active.

One timing primitive, `span(name, **attrs)`, times every layer boundary of
the served v1 path, traced or not: it folds the duration into the request's
`PhaseLedger` (per span name: total, self, count and cpu — what every broker
response carries as `spanTimesMs` / `spanSelfMs` / `spanCpuMs`, with the
request's `counters` and `deviceWork`), opens a `jax.profiler.TraceAnnotation` tagged
with the broker's query id (inert without a profiler session; under one the
span lies in the `.xplane.pb`'s host plane on the device trace's clock), and
joins the `RequestTrace` tree when one is active. `phase_timer` and
`InvocationScope` are `span` with a phase, or with a run-time name. The span
names and what reads each are listed in PERF.md section 3.

A span opened with `cpu=True` reads a second clock beside `perf_counter`:
`thread_time`, the CPU time of the thread that ran it (the entry's fourth
number, `spanCpuMs`). Where such a span has no I/O inside, total less cpu is
time its thread stood runnable and did not run: the interpreter's lock, or
the cores. The thread clock is a system call and not free — 0.3 us a read on
a plain kernel, 5.8 us under gVisor, where it also ticks at 10 ms (PERF.md
section 6, PR 37) — so it is read where a span asks, by the spans whose cpu
a per-layer metric reads (PERF.md section 3), and not at every span of
every query: a loop of many short spans is wrapped in one span that reads it
(`server.dispatch_all` around a query's `server.dispatch`).
"""

from __future__ import annotations

import contextvars
import threading
import time
import uuid
from dataclasses import dataclass, field
from enum import Enum

from jax.profiler import TraceAnnotation


class ServerQueryPhase(Enum):
    REQUEST_DESERIALIZATION = "requestDeserialization"
    TOTAL_QUERY_TIME = "totalQueryTime"
    SEGMENT_PRUNING = "segmentPruning"
    BUILD_QUERY_PLAN = "buildQueryPlan"
    QUERY_PLAN_EXECUTION = "queryPlanExecution"
    RESPONSE_SERIALIZATION = "responseSerialization"
    SCHEDULER_WAIT = "schedulerWait"
    #: kernel_obs' `deviceMs`: a launch's share of its query's one wait for
    #: the result vectors (kernels.wait_packed) — the first launch's holds
    #: everything queued on the device ahead of it, the later ones what was
    #: still to arrive: not the program's device time. The span
    #: `server.device_wait` is that wait whole, once a query; device time
    #: proper is in the profiler trace (perfbench `device_busy_ms_per_query`)
    DEVICE_EXECUTION = "deviceExecution"
    # broker/transport phases (BrokerQueryPhase parity) — one enum keeps the
    # phaseTimesMs namespace flat across roles
    REQUEST_COMPILATION = "requestCompilation"
    BROKER_REDUCE = "brokerReduce"
    MAILBOX_RECEIVE_WAIT = "mailboxReceiveWait"
    # the segment load path: the controller's upload (spans `controller.upload*`)
    # and the server's load of an assigned segment (`server.load`)
    SEGMENT_UPLOAD = "segmentUpload"
    SEGMENT_UPLOAD_UNTAR = "segmentUploadUntar"
    SEGMENT_UPLOAD_VERIFY = "segmentUploadVerify"
    SEGMENT_UPLOAD_PUBLISH = "segmentUploadPublish"
    SEGMENT_UPLOAD_TRANSITION = "segmentUploadTransition"
    SEGMENT_LOAD = "segmentLoad"


@dataclass
class TraceContext:
    """W3C traceparent-shaped propagation context: 32-hex trace id, 16-hex
    parent span id, sampled flag. Immutable per hop; the receiving process
    starts its subtree under `parent_span_id`."""

    trace_id: str
    parent_span_id: str
    sampled: bool = True

    @staticmethod
    def mint() -> "TraceContext":
        return TraceContext(uuid.uuid4().hex, uuid.uuid4().hex[:16], True)

    def to_header(self) -> str:
        # version 00, per https://www.w3.org/TR/trace-context/
        return f"00-{self.trace_id}-{self.parent_span_id}-{'01' if self.sampled else '00'}"

    @staticmethod
    def from_header(header: str) -> "TraceContext | None":
        parts = header.strip().split("-")
        if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
            return None
        return TraceContext(parts[1], parts[2], parts[3] == "01")

    def to_dict(self) -> dict:
        return {"traceId": self.trace_id, "parentSpanId": self.parent_span_id, "sampled": self.sampled}

    @staticmethod
    def from_dict(d: dict) -> "TraceContext":
        return TraceContext(d["traceId"], d["parentSpanId"], bool(d.get("sampled", True)))


@dataclass
class Span:
    name: str
    start_ms: float
    duration_ms: float = 0.0
    children: list = field(default_factory=list)
    attrs: dict = field(default_factory=dict)
    events: list = field(default_factory=list)

    def add_event(self, name: str, ts_ms: float, attrs: dict | None = None) -> None:
        ev = {"name": name, "tsMs": round(ts_ms, 3)}
        if attrs:
            ev["attrs"] = dict(attrs)
        self.events.append(ev)

    def to_dict(self) -> dict:
        d = {"name": self.name, "startMs": round(self.start_ms, 3), "durationMs": round(self.duration_ms, 3)}
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.events:
            d["events"] = [dict(e) for e in self.events]
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d


class RequestTrace:
    """Per-request span tree. Thread-safe: combine workers append concurrently.

    One instance per process per traced request: the broker's carries the
    root, each server builds its own and ships `subtree()` back for the
    broker to `add_remote()` and finally `assemble()`.
    """

    def __init__(self, request_id: str = "", context: TraceContext | None = None, service: str = "broker"):
        self.request_id = request_id
        self.context = context
        self.service = service
        self.root = Span("request" if service == "broker" else service, 0.0)
        self._t0 = time.perf_counter()
        # wall clock captured at the same instant as the perf_counter epoch:
        # lets the assembling broker align remote offsets despite clock skew
        self.anchor_wall_ms = time.time() * 1e3
        self._lock = threading.Lock()
        self.phase_ms: dict[str, float] = {}
        self.remote: list[dict] = []

    def now_ms(self) -> float:
        return (time.perf_counter() - self._t0) * 1e3

    def add_span(self, span: Span, parent: Span | None = None) -> None:
        with self._lock:
            (parent or self.root).children.append(span)

    def add_event(self, name: str, **attrs) -> None:
        """Record a point-in-time event on the root span (resilience-plane
        moments: retries, deadline hits, fault injections, kills)."""
        with self._lock:
            self.root.add_event(name, self.now_ms(), attrs or None)

    def add_remote(self, subtree: dict) -> None:
        """Attach a span subtree shipped back from another process."""
        if not isinstance(subtree, dict):
            return
        with self._lock:
            self.remote.append(subtree)

    def record_phase(self, phase: ServerQueryPhase, ms: float) -> None:
        with self._lock:
            self.phase_ms[phase.value] = self.phase_ms.get(phase.value, 0.0) + ms

    def record_phase_ms(self, name: str, ms: float) -> None:
        """String-keyed phase recording for phases outside ServerQueryPhase —
        the HTTP wire timeline folds its socket-level phases in here under
        `http.<name>` keys so /debug/traces/{id} shows transport time next
        to engine time."""
        with self._lock:
            self.phase_ms[name] = self.phase_ms.get(name, 0.0) + ms

    def to_dict(self) -> dict:
        with self._lock:
            d = {
                "requestId": self.request_id,
                "phaseTimesMs": {k: round(v, 3) for k, v in self.phase_ms.items()},
                "spans": [c.to_dict() for c in self.root.children],
            }
            if self.context is not None:
                d["traceId"] = self.context.trace_id
            if self.root.events:
                d["events"] = [dict(e) for e in self.root.events]
            if self.remote:
                d["processes"] = [dict(r) for r in self.remote]
            return d

    def subtree(self) -> dict:
        """Serializable span subtree for shipping back to the assembler."""
        d = self.to_dict()
        d["service"] = self.service
        d["anchorWallMs"] = round(self.anchor_wall_ms, 3)
        if self.context is not None:
            d["parentSpanId"] = self.context.parent_span_id
        return d

    def assemble(self) -> dict:
        """Flatten local + remote subtrees into one OTLP-flavored document.

        Remote span offsets are shifted by (remote anchor − local anchor) so
        all startMs share the broker's timeline. Span ids are synthetic and
        sequential — stable for a given trace, unique within it.
        """
        seq = [0]

        def next_id() -> str:
            seq[0] += 1
            return f"{seq[0]:016x}"

        def flatten(span_dict: dict, parent_id: str, shift_ms: float, out: list) -> None:
            sid = next_id()
            rec = {
                "spanId": sid,
                "parentSpanId": parent_id,
                "name": span_dict.get("name", ""),
                "startMs": round(span_dict.get("startMs", 0.0) + shift_ms, 3),
                "durationMs": span_dict.get("durationMs", 0.0),
            }
            if span_dict.get("attrs"):
                rec["attrs"] = span_dict["attrs"]
            if span_dict.get("events"):
                rec["events"] = [
                    {**e, "tsMs": round(e.get("tsMs", 0.0) + shift_ms, 3)} for e in span_dict["events"]
                ]
            out.append(rec)
            for child in span_dict.get("children", ()):
                flatten(child, sid, shift_ms, out)

        with self._lock:
            root_id = self.context.parent_span_id if self.context is not None else next_id()
            local_spans: list[dict] = [
                {
                    "spanId": root_id,
                    "parentSpanId": "",
                    "name": self.root.name,
                    "startMs": 0.0,
                    "durationMs": round(self.root.duration_ms, 3),
                }
            ]
            if self.root.events:
                local_spans[0]["events"] = [dict(e) for e in self.root.events]
            for child in self.root.children:
                flatten(child.to_dict(), root_id, 0.0, local_spans)
            resource_spans = [
                {
                    "resource": {"service.name": self.service},
                    "phaseTimesMs": {k: round(v, 3) for k, v in self.phase_ms.items()},
                    "spans": local_spans,
                }
            ]
            remote = [dict(r) for r in self.remote]

        for sub in remote:
            shift = float(sub.get("anchorWallMs", self.anchor_wall_ms)) - self.anchor_wall_ms
            parent = sub.get("parentSpanId") or root_id
            spans: list[dict] = []
            sub_root_id = next_id()
            rec = {
                "spanId": sub_root_id,
                "parentSpanId": parent,
                "name": sub.get("service", "remote"),
                "startMs": round(shift, 3),
                "durationMs": 0.0,
            }
            if sub.get("events"):
                rec["events"] = [
                    {**e, "tsMs": round(e.get("tsMs", 0.0) + shift, 3)} for e in sub["events"]
                ]
            spans.append(rec)
            for child in sub.get("spans", ()):
                flatten(child, sub_root_id, shift, spans)
            resource_spans.append(
                {
                    "resource": {"service.name": sub.get("service", "remote")},
                    "phaseTimesMs": sub.get("phaseTimesMs", {}),
                    "spans": spans,
                }
            )

        return {
            "traceId": self.context.trace_id if self.context is not None else "",
            "requestId": self.request_id,
            "resourceSpans": resource_spans,
        }


# active trace for the current execution context (None = tracing disabled,
# the no-op default). contextvars gives TraceRunnable-style propagation into
# threads when callers copy_context() (the query scheduler snapshots the
# submitting context per job; ad-hoc worker threads use run_traced).
_active: contextvars.ContextVar[RequestTrace | None] = contextvars.ContextVar("pinot_trace", default=None)


def active_trace() -> RequestTrace | None:
    return _active.get()


def trace_event(name: str, **attrs) -> None:
    """Record a point-in-time event on the active trace's root span.
    No-op (one ContextVar read) when tracing is off — safe on hot paths."""
    tr = _active.get()
    if tr is not None:
        tr.add_event(name, **attrs)


class start_trace:
    """Context manager enabling tracing for the dynamic extent of a request."""

    def __init__(self, request_id: str = "", context: TraceContext | None = None, service: str = "broker"):
        self.trace = RequestTrace(request_id, context=context, service=service)

    def __enter__(self) -> RequestTrace:
        self._token = _active.set(self.trace)
        return self.trace

    def __exit__(self, *exc):
        _active.reset(self._token)
        return False


# -- the request's phase ledger ------------------------------------------------


class PhaseLedger:
    """What one request spent where, per span name: total ms, self ms (the
    total less what child spans cover), count and cpu ms (the CPU time of the
    threads that ran the span, `time.thread_time`; None where the span does
    not read that clock, and for an interval folded by `record_span`, which
    no one thread saw whole); the request's counters
    (wire bytes, segments and rows dispatched) and the static work of the
    device programs it launched. One per request per role: the broker's is
    set at `Broker.execute` entry, a server's at its request entry (HTTP
    handler or in-process `execute_partials`); servers ship theirs back on
    element 3 of the partials tuple and the broker folds them in with
    `merge_servers`. Thread-safe: scatter legs and scheduler workers fold
    concurrently."""

    __slots__ = ("qid", "role", "_lock", "spans", "counters", "device_work")

    def __init__(self, qid: str = "", role: str = "broker"):
        self.qid = qid
        self.role = role
        self._lock = threading.Lock()
        self.spans: dict[str, list] = {}  # name -> [total ms, self ms, count, cpu ms | None]
        self.counters: dict[str, int] = {}
        self.device_work: dict[str, dict] = {}

    def fold(
        self, name: str, ms: float, self_ms: float, parent: "span | None" = None, cpu_ms: float | None = None
    ) -> None:
        with self._lock:
            ent = self.spans.get(name)
            if ent is None:
                self.spans[name] = [ms, self_ms, 1, cpu_ms]
            else:
                ent[0] += ms
                ent[1] += self_ms
                ent[2] += 1
                if cpu_ms is not None:
                    ent[3] = (ent[3] or 0.0) + cpu_ms
            if parent is not None:
                parent._child_ms += ms

    def pass_up(self, parent: "span", child_ms: float) -> None:
        with self._lock:
            parent._child_ms += child_ms

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + int(n)

    def add_device_work(self, program: str, rows: int, kernels: dict) -> None:
        """One launch of a fused per-segment program: its rows and the static
        work of the registered kernels traced into it (kernel_obs)."""
        with self._lock:
            _add_work(self.device_work, program, {"launches": 1, "rows": int(rows), "kernels": kernels})

    def to_wire(self) -> dict:
        with self._lock:
            return {
                "spans": {k: list(v) for k, v in self.spans.items()},
                "counters": dict(self.counters),
                "deviceWork": {
                    p: {**w, "kernels": {k: dict(c) for k, c in w["kernels"].items()}}
                    for p, w in self.device_work.items()
                },
            }

    def merge_servers(self, docs: list) -> None:
        """Fold the ledgers of the servers of one scatter into this one: the
        servers ran side by side, so the spans are those of the server with
        the longest `server.execute`, **taken whole** (the critical path: a
        span's max and another span's max could come from two servers, and
        `server.execute` less `server.device_wait` then described neither);
        counters and device work add up over all of them. Counters
        `serversMerged` and `scatterSkewMs` (the slowest `server.execute`
        less the fastest, in ms) say how far apart they ran."""
        if not docs:
            return

        def execute_ms(doc: dict) -> float:
            return doc.get("spans", {}).get("server.execute", (0.0,))[0]

        slowest = max(docs, key=execute_ms)
        with self._lock:
            for doc in docs:
                for name, n in doc.get("counters", {}).items():
                    self.counters[name] = self.counters.get(name, 0) + int(n)
                for program, work in doc.get("deviceWork", {}).items():
                    _add_work(self.device_work, program, work)
            self.counters["serversMerged"] = self.counters.get("serversMerged", 0) + len(docs)
            skew = execute_ms(slowest) - min(execute_ms(d) for d in docs)
            self.counters["scatterSkewMs"] = round(self.counters.get("scatterSkewMs", 0) + skew, 3)
            for name, (ms, self_ms, n, *cpu_ms) in slowest.get("spans", {}).items():
                ent = self.spans.setdefault(name, [0.0, 0.0, 0, None])
                ent[0] += ms
                ent[1] += self_ms
                ent[2] += n
                if cpu_ms and cpu_ms[0] is not None:  # a server from before the second clock sends three numbers
                    ent[3] = (ent[3] or 0.0) + cpu_ms[0]

    def response_fields(self) -> dict:
        """The five keys every v1 broker response carries."""
        doc = self.to_wire()
        work = doc["deviceWork"].values()
        return {
            "spanTimesMs": {k: round(v[0], 3) for k, v in doc["spans"].items()},
            "spanSelfMs": {k: round(v[1], 3) for k, v in doc["spans"].items()},
            "spanCpuMs": {k: round(v[3], 3) for k, v in doc["spans"].items() if v[3] is not None},
            "counters": {
                "wireRequestBytes": 0,
                "wireResponseBytes": 0,
                "serversMerged": 0,
                "scatterSkewMs": 0,
                # link crossings of the query (query/kernels.py): transfer calls host -> device (one a launch: its
                # operands go with it; one more when a stable operand is first staged) and waits device -> host
                "hostToDeviceTransfers": 0,
                "deviceReadbackWaits": 0,
                # limb reductions (a grouped DOUBLE SUM / AVG on the byte-plane kernel) whose rows did not fit
                # their exponent window and took the scatter; those dispatched are `deviceWork`'s to tell
                "groupedLimbFallbacks": 0,
                # stages of a group-by reduce (HAVING, ORDER BY, the select list) that left the columns for a
                # dict a group or the comparison sort (query/reduce.py `reduce_group_by`)
                "reduceRowStages": 0,
                # first stagings host -> HBM inside the query (segment/segment.py `to_device`, span `server.stage`)
                "segmentsStaged": 0,
                # lookUp on the device (query/plan.py `lookup_node`, query/kernels.py `_lookup_codes`): fk code ->
                # destination code operands the query had to build, their bytes (staged to the chip with its
                # launches) — both 0 once every (segment, foreign key, table generation, destination) it reads
                # has been read before — and the rows it launched whose foreign key had no dimension row
                "lookupOperandBuilds": 0,
                "lookupOperandBytesStaged": 0,
                "lookupMisses": 0,
                # the star-tree swap (query/startree_exec.py, engine._dispatch_segment): segments of the query whose
                # program was launched over a star table in their place, the records those tables hold, and the star
                # tables wrapped as segments inside the query (a table's first use, which the dispatch behind it stages)
                "starTreeSegments": 0,
                "starTreeRecords": 0,
                "starTreeBuilds": 0,
                # the compact group space (query/plan.py `group_spec`'s "groups_compact", query/kernels.py
                # `_compact_groups`): segments of the query launched under it, and those of them whose groups passed
                # its slots and were launched again under the plan they had before it (engine._launch_again)
                "groupCompactSegments": 0,
                "groupCompactFallbacks": 0,
                **doc["counters"],
                # what was dispatched is what `deviceWork` holds, program by program
                "segmentsDispatched": sum(w["launches"] for w in work),
                "rowsDispatched": sum(w["rows"] for w in work),
            },
            "deviceWork": doc["deviceWork"],
        }


def _add_work(into: dict, program: str, work: dict) -> None:
    ent = into.setdefault(program, {"launches": 0, "rows": 0, "kernels": {}})
    ent["launches"] += int(work.get("launches", 0))
    ent["rows"] += int(work.get("rows", 0))
    for kernel, c in work.get("kernels", {}).items():
        k = ent["kernels"].setdefault(kernel, {"calls": 0, "bytes": 0.0, "flops": 0.0})
        for key in ("calls", "bytes", "flops"):
            k[key] += c.get(key, 0)


_ledger: contextvars.ContextVar[PhaseLedger | None] = contextvars.ContextVar("pinot_ledger", default=None)
# innermost open span of this execution context: what a new span nests under
_open: contextvars.ContextVar["span | None"] = contextvars.ContextVar("pinot_open_span", default=None)


def active_ledger() -> PhaseLedger | None:
    return _ledger.get()


def count(name: str, n: int = 1) -> None:
    """Add to a counter of the request's ledger; no-op outside a request."""
    led = _ledger.get()
    if led is not None:
        led.count(name, n)


class request_ledger:
    """The dynamic extent of one request in one role. A role's outer entry
    (its HTTP handler) and inner entry (`Broker.execute`, the server's
    `execute_partials`) share one ledger; another role's (an in-process
    server under the broker's thread) starts its own, as a remote server
    would."""

    __slots__ = ("qid", "role", "_tokens")

    def __init__(self, qid: str = "", role: str = "broker"):
        self.qid = qid
        self.role = role
        self._tokens = None

    def __enter__(self) -> PhaseLedger:
        cur = _ledger.get()
        if cur is not None and cur.role == self.role:
            if self.qid and not cur.qid:
                cur.qid = self.qid  # opened before the id was minted (the broker's HTTP handler)
            return cur
        ledger = PhaseLedger(self.qid, self.role)
        self._tokens = (_ledger.set(ledger), _open.set(None))
        return ledger

    def __exit__(self, *exc):
        if self._tokens is not None:
            _ledger.reset(self._tokens[0])
            _open.reset(self._tokens[1])
        return False


class span:
    """The one timing primitive: `with span("server.dispatch", segment=...)`.

    Always: perf_counter at entry and exit — with `cpu=True` thread_time
    too — folded into the request's phase ledger (total, self, count, cpu;
    nesting from the context, so a span opened in a scheduler worker lands
    under the span that submitted it), and —
    under a profiler session — a `jax.profiler.TraceAnnotation(name,
    qid=..., **attrs)`: the span lies in the `.xplane.pb`'s host plane, on
    the device trace's clock, with the request's id in it.
    When a RequestTrace is active: the `Span` joins its tree, under the
    enclosing span's. With `phase=`: the duration also feeds that
    ServerQueryPhase — the trace's `phaseTimesMs`, the `<role>.phase.*Ms`
    timer of `/metrics` when `role` is given, and the HTTP timeline's
    sub-phases. After exit `ms` holds the duration, `cpu_ms` the CPU time of
    the thread inside it (None without `cpu=True`), and `end` the
    `perf_counter` instant it closed at."""

    __slots__ = (
        "name", "attrs", "ms", "cpu_ms", "_phase", "_role", "_late", "_t0", "_cpu0", "_child_ms",
        "_ledger", "_parent", "_token", "_ann", "_trace", "_span",
    )  # fmt: skip

    #: False: a dynamic name (`segment:<name>`) stays out of the ledger, whose names are a fixed set
    _FOLD = True
    #: False: no `Span` in the active trace's tree
    _TREE = True

    def __init__(
        self, name: str, *, phase: ServerQueryPhase | None = None, role: str | None = None, cpu: bool = False, **attrs
    ):
        self.name = name
        self.attrs = attrs
        self.ms = 0.0
        self.cpu_ms = None
        self._cpu0 = 0.0 if cpu else None  # the thread clock's reading at entry; None: the span does not read it
        self._phase = phase
        self._role = role
        self._late = None
        self._span = None

    def __enter__(self) -> "span":
        led = self._ledger = _ledger.get()
        self._parent = _open.get()
        self._token = _open.set(self)
        self._child_ms = 0.0
        tr = self._trace = _active.get()
        if tr is not None and self._TREE:
            self._span = Span(self.name, tr.now_ms(), attrs=dict(self.attrs))
        # no profiler session (one static check): no annotation object at all
        ann = self._ann = (
            TraceAnnotation(self.name, qid=led.qid if led is not None else "", **self.attrs)
            if TraceAnnotation.is_enabled()
            else None
        )
        if ann is not None:
            ann.__enter__()
        if self._cpu0 is not None:
            self._cpu0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    @property
    def end(self) -> float:
        return self._t0 + self.ms * 1e-3

    def set_attr(self, key: str, value) -> None:
        """An attribute known only inside the span (rows matched, whether
        the dispatch compiled): reaches the profiler event and the tree."""
        if self._late is None:
            self._late = {}
        self._late[key] = value
        if self._span is not None:
            self._span.attrs[key] = value

    def _tree_parent(self) -> Span | None:
        """The nearest enclosing span with a `Span` in this trace's tree."""
        p = self._parent
        while p is not None and p._trace is self._trace:
            if p._span is not None:
                return p._span
            p = p._parent
        return None

    def __exit__(self, *exc):
        ms = self.ms = (time.perf_counter() - self._t0) * 1e3
        cpu_ms = None
        if self._cpu0 is not None:
            cpu_ms = self.cpu_ms = (time.thread_time() - self._cpu0) * 1e3
        ann = self._ann
        if ann is not None:
            if self._late:
                ann.set_metadata(**self._late)
            ann.__exit__(*exc)
        _open.reset(self._token)
        led = self._ledger
        if led is not None:
            parent = self._parent
            if parent is not None and parent._ledger is not led:
                parent = None
            if self._FOLD:
                led.fold(self.name, ms, max(ms - self._child_ms, 0.0), parent, cpu_ms)
            elif parent is not None and self._child_ms:
                led.pass_up(parent, self._child_ms)  # a span outside the ledger hides no child from its parent
        tr = self._trace
        if self._span is not None:
            self._span.duration_ms = ms
            tr.add_span(self._span, self._tree_parent())
        if self._phase is not None:
            _record_phase(self._phase, self._role, ms)
        return False


def _record_phase(phase: ServerQueryPhase, role: str | None, ms: float) -> None:
    """One ServerQueryPhase sample: the active trace's phaseTimesMs, the
    role's `<role>.phase.<phase>Ms` timer of `/metrics`, and the active HTTP
    wire timeline's sub-phase decomposition (no-op outside one)."""
    tr = _active.get()
    if tr is not None:
        tr.record_phase(phase, ms)
    if role is not None:
        from pinot_tpu.common.metrics import get_registry

        get_registry(role).timer(f"{role}.phase.{phase.value}Ms").update_ms(ms)
    from pinot_tpu.common.frontend_obs import record_timeline_sub

    record_timeline_sub(phase.value, ms)


def record_span(name: str, ms: float, phase: ServerQueryPhase | None = None, role: str | None = None) -> None:
    """Fold an interval that no one thread saw whole (a queue wait: submitted
    here, started there) into the ledger, as a child of the open span, and
    into `phase` as `span(phase=, role=)` would."""
    led = _ledger.get()
    if led is not None:
        parent = _open.get()
        led.fold(name, ms, ms, parent if parent is not None and parent._ledger is led else None)
    if phase is not None:
        _record_phase(phase, role, ms)


class InvocationScope(span):
    """Span around an operator invocation whose name is made at run time
    (`segment:<name>`, `stage3:w1`): timed and annotated by `span`, joins the
    active trace's tree under the root (or `parent`), stays out of the
    ledger. (Tracing.java InvocationScope parity.)"""

    __slots__ = ("_explicit_parent", "_off")
    _FOLD = False

    def __init__(self, name: str, parent: Span | None = None, **attrs):
        super().__init__(name, **attrs)
        self._explicit_parent = parent

    def __enter__(self) -> "InvocationScope":
        # outside the ledger, so with no trace to join and no profiler session
        # nothing would read it (Tracing.java's default NoOpTracer)
        self._off = _active.get() is None and not TraceAnnotation.is_enabled()
        return self if self._off else super().__enter__()

    def set_attr(self, key: str, value) -> None:
        if not self._off:
            super().set_attr(key, value)

    def __exit__(self, *exc):
        return False if self._off else super().__exit__(*exc)

    def _tree_parent(self) -> Span | None:
        return self._explicit_parent


class phase_timer(span):
    """Times one ServerQueryPhase (TimerContext parity) — `span` with
    `phase=`, for sites that have no span name of their own: the trace's
    phaseTimesMs when tracing is on, and — when `role` is given — that
    role's `<role>.phase.<phase>Ms` Timer unconditionally, so `/metrics`
    answers "which phase ate the budget" even for untraced queries. Stays
    out of the ledger, whose names are the span sites' own."""

    __slots__ = ()
    _FOLD = False
    _TREE = False

    def __init__(self, phase: ServerQueryPhase, role: str | None = None):
        super().__init__(f"phase.{phase.value}", phase=phase, role=role)


def bind_request(fn):
    """`fn` bound to the calling context's ledger, open span and trace — for
    pool threads, which inherit no context (the query scheduler copies the
    submitter's context by itself)."""
    led, parent, tr = _ledger.get(), _open.get(), _active.get()

    def bound(*args, **kwargs):
        def inner():
            _ledger.set(led)
            _open.set(parent)
            _active.set(tr)
            return fn(*args, **kwargs)

        return contextvars.copy_context().run(inner)

    return bound


def run_traced(trace: RequestTrace | None, fn, *args, **kwargs):
    """Run fn with `trace` active — the TraceRunnable analog for worker
    threads that did not inherit the submitting context."""
    if trace is None:
        return fn(*args, **kwargs)
    ctx = contextvars.copy_context()

    def _inner():
        _active.set(trace)
        return fn(*args, **kwargs)

    return ctx.run(_inner)
