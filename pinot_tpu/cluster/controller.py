"""Controller: table/schema management, segment assignment, ideal state.

Reference parity: PinotHelixResourceManager (pinot-controller/.../helix/core/
PinotHelixResourceManager.java:192 — tables, schemas, instances, ideal
states), segment assignment strategies (controller/helix/core/assignment/
segment/OfflineSegmentAssignment.java: balanced instance pick by segment
count; replica groups), and the segment upload path (addNewSegment -> ideal
state update -> server state transition). Our state transitions are
synchronous calls onto the server objects/endpoints (the Helix
OFFLINE->ONLINE message analog); the external view equals the ideal state
once those calls return.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
import zlib
from pathlib import Path

from pinot_tpu.common.config import TableConfig
from pinot_tpu.common.errors import ServerTimedOut
from pinot_tpu.common.trace import ServerQueryPhase
from pinot_tpu.common.types import Schema
from pinot_tpu.cluster.metadata import PropertyStore
from pinot_tpu.cluster.routing import RouteSnapshot
from pinot_tpu.segment.builder import write_segment
from pinot_tpu.segment.segment import ImmutableSegment

_LOG = logging.getLogger("pinot_tpu.controller")

#: the counter that every write to an `/instances/{server}` document moves
INSTANCES_VERSION_PATH = "/instancesversion"


def routing_version_path(table: str) -> str:
    """The counter document that every write to `table`'s routing state moves."""
    return f"/tables/{table}/routingversion"


class Controller:
    #: optional AccessControl SPI enforced by the HTTP endpoints
    access_control = None
    #: bound by PeriodicTaskScheduler(controller=...) — the /health/ready
    #: "periodicScheduler" component reports on it when present
    periodic_scheduler = None
    #: bound by ClusterMetricsAggregator(controller) — serves /debug/cluster
    #: and /debug/alerts on the controller HTTP surface
    cluster_aggregator = None

    def __init__(self, store: PropertyStore, deep_store: str | Path, controller_id: str = "controller_0"):
        """deep_store: directory holding uploaded segment dirs (the PinotFS
        deep-store analog: segments are durable here; servers load from it)."""
        self.store = store
        self.deep_store = Path(deep_store)
        self.deep_store.mkdir(parents=True, exist_ok=True)
        self.controller_id = controller_id
        self._servers: dict[str, object] = {}  # server_id -> Server handle
        self._election = None
        self._transitions = None
        #: held from the choice of a new segment's servers until its entry stands in the ideal state
        self._assign_lock = threading.Lock()
        #: (table, segment, server) of the transitions an upload is waiting for right now: drift the
        #: reconciler must not take for loss (a 181 MB segment loads for longer than its grace)
        self._loading: set[tuple[str, str, str]] = set()
        #: server id -> the instant (perf_counter) its session was reset, until its last replica is ONLINE again
        self._restoring: dict[str, float] = {}
        self._session_lock = threading.Lock()

    def readiness(self) -> "tuple[bool, dict]":
        """(ready, per-component detail) for GET /health/ready — the broker/
        server readiness contract extended to the controller: the property
        store must answer, a configured periodic scheduler must actually be
        running, and with HA enabled the lease state must be known (election
        thread alive — leader or standby both count as known)."""
        components: dict[str, dict] = {}
        try:
            self.store.list("/instances/")
            components["propertyStore"] = {"ok": True}
        except Exception as e:  # noqa: BLE001  # pinotlint: disable=deadline-swallow — readiness probe, off the query path; the failure is the signal
            components["propertyStore"] = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        sched = self.periodic_scheduler
        if sched is None:
            components["periodicScheduler"] = {"ok": True, "configured": False}
        else:
            running = bool(getattr(sched, "_running", False))
            components["periodicScheduler"] = {
                "ok": running,
                "configured": True,
                "tasks": [t.name for t in sched.tasks],
            }
        if self._election is None:
            components["ha"] = {"ok": True, "enabled": False}
        else:
            thread = getattr(self._election, "_thread", None)
            known = thread is not None and thread.is_alive()
            components["ha"] = {"ok": known, "enabled": True, "leader": self.is_leader}
        return all(c["ok"] for c in components.values()), components

    # -- high availability (cluster/ha.py) -----------------------------------

    def enable_ha(self, lease_ttl: float = 2.0, renew_every: float = 0.4) -> None:
        """Join lead-controller election and start the async transition
        worker (lead-controller partitioning + Helix message queue analog;
        PinotHelixResourceManager.java:192). Safe on multiple controllers
        sharing one store: only the lease holder acts."""
        from pinot_tpu.cluster.ha import LeaderElection, TransitionManager

        if self._election is not None:
            self.stop_ha()  # re-enable replaces, never leaks threads
        self._election = LeaderElection(self.store, self.controller_id, lease_ttl, renew_every)
        self._transitions = TransitionManager(self, self._election)
        self._election.start()
        self._transitions.start()

    def stop_ha(self, release_lease: bool = True) -> None:
        """Stop participating (simulates controller death when
        release_lease=False: standbys must wait out the lease TTL). Clears
        the transition manager too: with no worker to drain it, routing
        upload failures into the queue would silently lose replicas."""
        if self._transitions is not None:
            self._transitions.stop()
            self._transitions = None
        if self._election is not None:
            self._election.stop(release=release_lease)
            self._election = None

    @property
    def is_leader(self) -> bool:
        return self._election is None or self._election.is_leader

    def lease_fence(self) -> int | None:
        """Fencing token (lease epoch) lead-path store mutations carry so
        the store rejects them once a newer lease exists. None when HA is
        off — single-controller deployments stay unfenced."""
        return self._election.epoch if self._election is not None else None

    def register_controller_endpoint(self, host: str, port: int) -> None:
        """Publish this controller's HTTP endpoint so standbys' `leaderUrl`
        hints and client failover can locate whoever holds the lease."""
        self.store.set(f"/controllers/{self.controller_id}", {"host": host, "port": port})  # pinotlint: disable=fence-discipline — deliberately unfenced: STANDBYS must publish their endpoint too (leaderUrl redirects + client failover depend on it), and a standby holds no lease epoch to fence with

    def leader_url(self) -> str | None:
        """Base URL of the current lease holder, or None when unknown (no
        lease, or the holder never registered an HTTP endpoint)."""
        from pinot_tpu.cluster.metadata import LEASE_PATH

        lease = self.store.get(LEASE_PATH) or {}
        owner = lease.get("owner") or ""
        if not owner:
            return None
        doc = self.store.get(f"/controllers/{owner}") or {}
        if not doc.get("port"):
            return None
        return f"http://{doc['host']}:{doc['port']}"

    def ha_status(self) -> dict:
        """controller.ha.* observability block for /debug/cluster and
        GET /leader: lease role, fencing epoch, takeover/fenced-write
        counters."""
        from pinot_tpu.common.metrics import controller_metrics

        return {
            "enabled": self._election is not None,
            "controllerId": self.controller_id,
            "isLeader": self.is_leader,
            "leaseEpoch": self._election.epoch if self._election is not None else 0,
            "takeovers": self._election.takeovers if self._election is not None else 0,
            "fencedWrites": int(controller_metrics().meter("controller.ha.fencedWrites").count),
            "leaderUrl": self.leader_url(),
        }

    # -- instances -----------------------------------------------------------

    def register_server(
        self, server_id: str, handle=None, host: str = "local", port: int = 0, tags: list[str] | None = None
    ) -> None:
        """handle=None with a port registers a remote (HTTP) server — the
        cross-process Helix-participant analog; a RemoteServerClient is built
        lazily from the instance doc. `tags` carry tenant/tier membership
        ("<tenant>_OFFLINE", "hot_tier", ...); untagged servers belong to
        the DefaultTenant."""
        known = self.store.get(f"/instances/{server_id}")
        prev = known or {}
        # a re-registration without tags (server restart) must not wipe
        # operator-assigned tenant/tier tags
        eff_tags = list(tags) if tags is not None else prev.get("tags", [])
        # fenced: instance registration is a leader-only mutation (HTTP gates
        # standbys already); a deposed lead must not resurrect stale liveness.
        # `session` counts the registrations: a broker that had marked the
        # server down lets its next session in (`Broker._route_snapshot`)
        self.store.set(
            f"/instances/{server_id}",
            {"host": host, "port": port, "alive": True, "tags": eff_tags, "session": int(prev.get("session", 0)) + 1},
            fence=self.lease_fence(),
            bump=INSTANCES_VERSION_PATH,
        )
        if handle is not None:
            self._servers[server_id] = handle
        else:
            # a remote handle follows the instance document (`servers`); a
            # handle registered in process gives way to the endpoint
            self._servers.pop(server_id, None)
            if known is not None:
                self._reset_server_session(server_id)

    def _reset_server_session(self, server_id: str) -> None:
        """A server that registers again over HTTP is a new session (the Helix
        participant's ZK session analog): what its last one held is gone with
        the process, so its entries leave every table's external view. The
        reconciler then sees ideal state and view apart and enqueues its
        replicas again, and no broker routes to it for a segment before the
        view says ONLINE again. Left standing, the entries kept a restarted
        server ready and empty for ever (PERF.md, PR 31)."""
        from pinot_tpu.common.metrics import ControllerMeter, controller_metrics
        from pinot_tpu.common.trace import span

        dropped = 0

        def forget(view: dict | None) -> dict | None:
            nonlocal dropped
            held = [seg for seg, replicas in (view or {}).items() if server_id in replicas]
            if not held:
                return None
            dropped += len(held)
            kept = {seg: {s: st for s, st in replicas.items() if s != server_id} for seg, replicas in view.items()}
            return {seg: replicas for seg, replicas in kept.items() if replicas}

        with span("controller.serverSessionReset", server=server_id) as reset:
            for table in self.tables():
                self._update_external_view(table, forget)
            reset.set_attr("replicas", dropped)
        controller_metrics().meter(ControllerMeter.SERVER_SESSION_RESETS).mark()
        with self._session_lock:
            if dropped:
                self._restoring[server_id] = time.perf_counter()
            else:
                self._restoring.pop(server_id, None)

    def _replica_online(self, server_id: str) -> None:
        """A replica of `server_id` was confirmed ONLINE: where that was the
        last one a session reset took from it, the time from its
        re-registration goes to `controller.serverSessionRestoreMs`."""
        with self._session_lock:
            since = self._restoring.get(server_id)
        if since is None:
            return
        for table in self.tables():
            view = self.store.get(f"/tables/{table}/externalview") or {}
            for seg, replicas in self.ideal_state(table).items():
                if replicas.get(server_id) == "ONLINE" and view.get(seg, {}).get(server_id) != "ONLINE":
                    return
        with self._session_lock:
            if self._restoring.pop(server_id, None) is None:
                return
        from pinot_tpu.common.metrics import ControllerTimer, controller_metrics

        controller_metrics().timer(ControllerTimer.SERVER_SESSION_RESTORE).update_ms((time.perf_counter() - since) * 1e3)

    def update_server_tags(self, server_id: str, tags: list[str]) -> None:
        """Re-tag a server (updateInstanceTags REST parity)."""
        doc = self.store.get(f"/instances/{server_id}") or {}
        doc["tags"] = list(tags)
        self.store.set(f"/instances/{server_id}", doc, fence=self.lease_fence(), bump=INSTANCES_VERSION_PATH)

    def instances(self) -> dict[str, dict]:
        """server id -> instance document."""
        return {p.split("/")[-1]: self.store.get(p) or {} for p in self.store.list("/instances/")}

    def servers(self, instances: dict[str, dict] | None = None) -> dict[str, object]:
        """server id -> handle: the registered in-process objects, and a
        RemoteServerClient (kept) for every other instance that listens on a
        port. A kept client follows the instance document: one built for an
        endpoint the server has since left (a restart on another port, seen
        by another controller, or read between a registration's two steps)
        would take every delivery to a dead port for good."""
        from pinot_tpu.cluster.http import RemoteServerClient

        out = dict(self._servers)
        for sid, doc in (self.instances() if instances is None else instances).items():
            if not doc.get("port"):
                continue
            held = out.get(sid)
            if held is None or (
                isinstance(held, RemoteServerClient) and held.base_url != RemoteServerClient.url_of(doc)
            ):
                out[sid] = self._servers[sid] = RemoteServerClient.of_instance(doc)
        return out

    # -- brokers (DynamicBrokerSelector's ZK external-view analog) -----------

    def register_broker(self, broker_id: str, host: str, port: int) -> None:
        self.store.set(f"/brokers/{broker_id}", {"host": host, "port": port}, fence=self.lease_fence())

    def brokers(self) -> dict[str, str]:
        """broker_id -> base URL."""
        out = {}
        for path in self.store.list("/brokers/"):
            doc = self.store.get(path) or {}
            out[path.split("/")[-1]] = f"http://{doc['host']}:{doc['port']}"
        return out

    # -- schemas / tables ----------------------------------------------------

    def add_schema(self, schema: Schema) -> None:
        # fenced: config mutations from a stale ex-leader (lease lost while
        # it was paused/partitioned) must bounce like any other lead write
        # a schema is routing state of the table of its name (star expansion,
        # the cached plan): the write moves that table's routing version
        self.store.set(
            f"/schemas/{schema.name}",
            {"json": schema.to_json()},
            fence=self.lease_fence(),
            bump=routing_version_path(schema.name),
        )

    def get_schema(self, name: str) -> Schema | None:
        doc = self.store.get(f"/schemas/{name}")
        return Schema.from_json(doc["json"]) if doc else None

    def add_table(self, config: TableConfig) -> None:
        fence = self.lease_fence()
        # config (re)writes can change plans/pruning: treat as a routing change
        bump = routing_version_path(config.table_name)
        self.store.set(f"/tables/{config.table_name}/config", {"json": config.to_json()}, fence=fence, bump=bump)
        self.store.update(
            f"/tables/{config.table_name}/idealstate", lambda cur: {} if cur is None else None, fence=fence, bump=bump
        )

    # -- routing versions and the route snapshot ------------------------------
    # One monotonic counter per table, moved by EVERY write to what a broker
    # routes that table's queries on: its config, the schema of its name, its
    # segments' metadata, its ideal state (upload, delete, refresh,
    # rebalance move, realtime state change, deep-store repair) and its
    # external view (a replica confirmed or lost; still in a cluster where
    # nothing loads or dies, so the steady state stays one call a query). A second
    # counter, `INSTANCES_VERSION_PATH`, moves with every write to an instance
    # document (a server registering, a re-tag). Each such write names its
    # counter as `bump=` of the store call itself, so the write and the count
    # are one step of the store: no reader is told "unchanged" about a
    # document that has changed. The counters of a logical table and of its
    # `_REALTIME` twin and the instances' counter are the *token* of the
    # table's route snapshot (`route_snapshot`): the broker holds the
    # snapshot, asks once a query whether the token still stands, and keys
    # its plan and result caches on it — no flush protocol exists or is
    # needed. A counter outlives its table (`delete_table` leaves it and
    # moves it), so a table dropped and made again never repeats a token.
    # The pinotlint `cache-invalidation` checker holds every such write to
    # its `bump=`.

    def bump_routing_version(self, table: str) -> int:
        """Increment and return the table's routing version: for what changes
        a table's answers without a write to the documents above."""
        return self.store.bump(routing_version_path(table), fence=self.lease_fence())

    def routing_version(self, table: str) -> int:
        """The table's current routing version (0 = never mutated/unknown)."""
        return self.store.counter(routing_version_path(table))

    def _route_token(self, table: str, rt_name: str | None) -> str:
        twin = self.routing_version(rt_name) if rt_name else 0
        return f"{self.routing_version(table)}.{twin}.{self.store.counter(INSTANCES_VERSION_PATH)}"

    def route_snapshot(self, table: str, have: str | None = None) -> RouteSnapshot | None:
        """Everything a broker routes a query on `table` from, as one
        document under one token (`routing.RouteSnapshot`): the configs of
        the table and of its `_REALTIME` twin (None where there is none), the
        schema, every segment's metadata, the ideal state and the external
        view of both, and the server handles. None where `have` is the current token: the caller's
        document still stands. The document is read in one section of the
        store that no write lands in, and every write that it holds moves the
        token in the store call that makes it, so token and content agree."""
        rt_name = None if table.endswith("_REALTIME") else f"{table}_REALTIME"
        if have is not None and have == self._route_token(table, rt_name):
            return None
        with self.store.consistent_read():
            physical = [table] + ([rt_name] if rt_name else [])
            instances = self.instances()
            return RouteSnapshot(
                table,
                self._route_token(table, rt_name),
                self.get_table(table),
                self.get_table(rt_name) if rt_name else None,
                self.get_schema(table) or (self.get_schema(rt_name) if rt_name else None),
                {t: self.all_segment_metadata(t) for t in physical},
                {t: self.ideal_state(t) for t in physical},
                self.servers(instances),
                instances,
                {t: self.external_view(t) for t in physical},
            )

    def get_table(self, name: str) -> TableConfig | None:
        doc = self.store.get(f"/tables/{name}/config")
        return TableConfig.from_json(doc["json"]) if doc else None

    def tables(self) -> list[str]:
        return [p.split("/")[2] for p in self.store.list("/tables/") if p.endswith("/config")]

    def delete_table(self, name: str) -> int:
        """Drop a table: every segment (server unload + deep-store cleanup),
        and then the ENTIRE
        /tables/{name}/ subtree — pauseStatus, watermarks, and any other
        table-scoped key would otherwise poison a recreated table
        (DeleteTableCommand / PinotHelixResourceManager.deleteOfflineTable
        parity). Returns the number of segments removed."""
        segs = [
            p.split("/")[-1]
            for p in self.store.list(f"/tables/{name}/segments/")
        ]
        for s in segs:
            self.delete_segment(name, s)
        counter = routing_version_path(name)
        for p in list(self.store.list(f"/tables/{name}/")):
            if p != counter:  # it outlives the table: a table made again repeats no token
                self.store.delete(p, fence=self.lease_fence(), bump=counter)
        return len(segs)

    def delete_schema(self, name: str) -> None:
        """Drop a schema (DeleteSchemaCommand parity). Refuses while a table
        still uses it — the reference's referential guard."""
        if name in self.tables():
            raise ValueError(f"schema {name!r} is still used by table {name!r}; delete the table first")
        self.store.delete(f"/schemas/{name}", fence=self.lease_fence(), bump=routing_version_path(name))

    # -- segment upload & assignment ----------------------------------------

    def upload_segment(self, table: str, segment: ImmutableSegment) -> list[str]:
        """Write segment to the deep store, VERIFY the written bytes, then
        assign replicas and push state transitions to the chosen servers.
        Returns the assigned server ids.

        Ordering contract (write → verify → assign): no cluster metadata —
        segment doc, ideal state, server transition — may reference the
        deep-store dir until the on-disk image passes whole-file CRC
        verification. A failed or short write (ENOSPC, crash, disk fault)
        surfaces as a typed SegmentUploadError and removes the partial dir,
        so later downloads can never reference half a segment."""
        config = self._table_for_upload(table)
        from pinot_tpu.segment.store import SEGMENT_FILE, verify_segment_file

        table_dir = self.deep_store / table
        seg_dir = table_dir / segment.name
        created = [] if seg_dir.exists() else [seg_dir, *([] if table_dir.exists() else [table_dir])]
        with self._landing(table, segment.name, created):
            seg_dir = write_segment(segment, table_dir)
            file_crc = verify_segment_file(seg_dir) if (seg_dir / SEGMENT_FILE).exists() else None
        stats = {
            col: {"min": ci.stats.to_dict()["min"], "max": ci.stats.to_dict()["max"], "cardinality": ci.cardinality}
            for col, ci in segment.columns.items()
        }
        return self._publish(
            table, config, segment.name, seg_dir, segment.n_docs, stats, file_crc, self._compute_partitions(segment, config)
        )

    def upload_segment_archive(self, table: str, archive: bytes) -> tuple[str, list[str]]:
        """The HTTP entry: a gzipped tar of a segment directory. The directory
        lands in the deep store **as uploaded** — the client already sent the
        deep store's own format, so nothing is decoded or encoded again:
        untar into a temporary directory inside `<deep store>/<table>/`, fsync,
        verify the landed file's whole-file CRC, read name, numDocs and the
        columns' stats from the file's own index map, rename into place, then
        the same assign-and-publish step as `upload_segment`, whose ordering
        contract holds here too. Only a table that declares partitioning or is
        a dimension table has the segment decoded. Returns (segment name,
        assigned server ids)."""
        import os
        import shutil
        import tempfile

        from pinot_tpu.common.durability import fsync_dir
        from pinot_tpu.common.trace import span
        from pinot_tpu.segment.store import SEGMENT_FILE, SegmentFileReader, dictionary_cardinality

        config = self._table_for_upload(table)
        table_dir = self.deep_store / table
        with span("controller.upload", phase=ServerQueryPhase.SEGMENT_UPLOAD, role="controller", bytes=len(archive)) as up:
            table_dir_existed = table_dir.exists()
            table_dir.mkdir(parents=True, exist_ok=True)
            tmp = Path(tempfile.mkdtemp(prefix=".upload-", dir=table_dir))
            with self._landing(table, "<archive>", [tmp] if table_dir_existed else [tmp, table_dir]):
                with span("controller.upload.untar", phase=ServerQueryPhase.SEGMENT_UPLOAD_UNTAR, role="controller"):
                    self._untar(archive, tmp)
                    entries = list(tmp.iterdir())
                    seg_root = entries[0] if len(entries) == 1 and entries[0].is_dir() else tmp
                    if not (seg_root / SEGMENT_FILE).exists():
                        # the v1 layout (metadata.json + columns.npz): decoded, and written as the deep store writes
                        from pinot_tpu.segment.loader import load_segment

                        segment = load_segment(seg_root)
                        shutil.rmtree(tmp)
                        if not table_dir_existed:
                            table_dir.rmdir()  # `upload_segment` makes it, and takes it away again if it fails
                        up.set_attr("segment", segment.name)
                        return segment.name, self.upload_segment(table, segment)
                with span("controller.upload.verify", phase=ServerQueryPhase.SEGMENT_UPLOAD_VERIFY, role="controller"):
                    reader = SegmentFileReader(seg_root / SEGMENT_FILE)  # verifies the whole-file CRC, decodes no entry
                    file_crc, meta = reader.file_crc, reader.meta
                    del reader  # and with it the file's memory map, before the file changes place
                    name = meta["segmentName"]
                    up.set_attr("segment", name)
                    if Path(name).name != name or name.startswith("."):
                        raise OSError(f"segment name {name!r} is no directory name")
                seg_dir = table_dir / name
                if seg_dir.exists():  # a refresh: the files change place one by one, each atomically
                    for f in seg_root.iterdir():
                        os.replace(f, seg_dir / f.name)
                else:
                    os.rename(seg_root, seg_dir)
                fsync_dir(seg_dir)
                fsync_dir(table_dir)
                shutil.rmtree(tmp, ignore_errors=True)
            stats = {}
            for cm in meta["columns"]:
                card = dictionary_cardinality(meta, cm["name"])
                stats[cm["name"]] = {
                    "min": cm["stats"]["min"],
                    "max": cm["stats"]["max"],
                    "cardinality": cm["stats"]["cardinality"] if card is None else card,
                }
            partitions = {}
            if (config.extra or {}).get("segmentPartitionConfig"):
                from pinot_tpu.segment.loader import load_segment

                partitions = self._compute_partitions(load_segment(seg_dir), config)
            return name, self._publish(table, config, name, seg_dir, int(meta["numDocs"]), stats, file_crc, partitions)

    #: the load path moves a segment in pieces of this size
    _PIECE = 4 << 20

    @classmethod
    def _untar(cls, archive: bytes, dest: Path) -> None:
        """The archive's directories and regular files under `dest`, each file
        fsynced (durable before any metadata names it, as `atomic_write_bytes`
        makes it). Two passes, both in pieces of 4 MB: the gzip stream is
        inflated into a temporary tar file, then each member is copied out of
        it. Five uploads at once took 5.3 s a 181 MB segment for 1.7 s alone
        (four chips' host; PERF.md, PR 27): tarfile's own loop moves 16 kB at
        a time and the uploads took turns at the interpreter lock, and
        inflating a whole archive into memory made them take turns at the
        process's page tables instead (5.2 s each for 1.9 s alone on the
        CPU). Members pass tarfile's `data` filter, so none leaves `dest`;
        anything but a directory or a regular file is refused."""
        import os
        import tarfile

        tar_path = dest / ".archive.tar"
        try:
            view = memoryview(archive)
            with open(tar_path, "wb") as out:
                inflater = zlib.decompressobj(wbits=31)
                for i in range(0, len(view), cls._PIECE):
                    data = view[i : i + cls._PIECE]
                    while True:
                        out.write(inflater.decompress(data))
                        data = inflater.unused_data if inflater.eof else b""
                        if not data:
                            break
                        inflater = zlib.decompressobj(wbits=31)  # a gzip file may hold several members
                if not inflater.eof:
                    raise EOFError("the gzip stream ends early")
            with open(tar_path, "rb") as src, tarfile.open(fileobj=src, mode="r:") as tf:
                for member in tf:
                    member = tarfile.data_filter(member, str(dest))
                    target = dest / member.name
                    if member.isdir():
                        target.mkdir(parents=True, exist_ok=True)
                    elif member.isreg():
                        target.parent.mkdir(parents=True, exist_ok=True)
                        back = src.tell()
                        src.seek(member.offset_data)
                        with open(target, "wb") as f:
                            left = member.size
                            while left:
                                piece = src.read(min(left, cls._PIECE))
                                if not piece:
                                    raise EOFError("the archive ends inside a member")
                                f.write(piece)
                                left -= len(piece)
                            f.flush()
                            os.fsync(f.fileno())
                        src.seek(back)
                    else:
                        raise tarfile.TarError(f"{member.name!r} is neither a directory nor a regular file")
        except (tarfile.TarError, EOFError, zlib.error) as e:
            raise OSError(f"unreadable archive: {e}") from e
        finally:
            tar_path.unlink(missing_ok=True)

    def _table_for_upload(self, table: str) -> TableConfig:
        config = self.get_table(table)
        if config is None:
            raise KeyError(f"no such table: {table}")
        return config

    @contextlib.contextmanager
    def _landing(self, table: str, what: str, created: list[Path]):
        """The write-and-verify half of an upload: a disk fault or a failed
        verification inside it removes `created` (directories this upload
        made, innermost first) and leaves as a typed SegmentUploadError."""
        from pinot_tpu.common.errors import SegmentCorruptedError, SegmentUploadError

        try:
            yield
        except SegmentUploadError:
            raise  # an inner landing's, which has tidied up after itself
        except (OSError, SegmentCorruptedError) as e:
            import shutil

            for d in created[:1]:
                shutil.rmtree(d, ignore_errors=True)
            for d in created[1:]:
                # the table's first segment: drop the dir the failed write
                # made, so that the deep store is exactly as before
                with contextlib.suppress(OSError):
                    d.rmdir()
            raise SegmentUploadError(
                getattr(e, "errno", None) or 0,
                f"segment upload {table}/{what} failed, no partial dir left: {e}",
            ) from e

    #: how long an upload's acknowledgement waits for the view to confirm a replica whose server took the
    #: transition and did not answer in time (`_publish`): a transition's own time once more
    TRANSITION_CONFIRM_S = 60.0

    def _publish(
        self, table: str, config: TableConfig, name: str, seg_dir: Path, n_docs: int, stats: dict,
        file_crc: int | None, partitions: dict,
    ) -> list[str]:  # fmt: skip
        """The assign half, shared by both entries: the segment's servers
        chosen, its metadata written and its entry put into the ideal state
        as one step (`_assign_lock`), each write moving the routing version
        with it, then the servers' state transitions."""
        from pinot_tpu.common.trace import span

        seg_meta = {"numDocs": n_docs, "location": str(seg_dir), "stats": stats}
        if file_crc is not None:
            # cluster truth for downloaders/scrubbers: a copy whose bytes
            # don't hash to this is corrupt no matter what its footer says
            seg_meta["fileCrc"] = file_crc
        if partitions:
            seg_meta["partitions"] = partitions
        loading: list[tuple[str, str, str]] = []
        try:
            with span("controller.upload.publish", phase=ServerQueryPhase.SEGMENT_UPLOAD_PUBLISH, role="controller"):
                # Choosing the servers and entering them are one step for the uploads of this controller
                # (the leader is the only writer): of two uploads that end together the second counts the
                # first's entry, so a table comes out even however its uploads interleave, and neither
                # entry is lost (seed 3260000704 lost one; PERF.md, PR 26). The metadata is written before
                # the ideal state names the segment, as the reconciler and the brokers expect.
                with self._assign_lock:
                    assigned = self._assign(table, config.replication)
                    seg_meta.update(servers=assigned, uploadedAt=time.time())
                    self.write_segment_metadata(table, name, seg_meta)
                    loading = [(table, name, sid) for sid in assigned]
                    self._loading.update(loading)  # before the ideal state shows them: the reconciler leaves them be

                    def enter(ideal: dict | None) -> dict:
                        return {**(ideal or {}), name: dict.fromkeys(assigned, "ONLINE")}

                    self._update_ideal_state(table, enter)
            # state transition: servers load the segment from the deep store.
            # With HA enabled, a failing server falls back to the durable retry
            # queue instead of failing the upload (Helix async transition analog).
            handles = self.servers()
            at_work: list[str] = []
            for sid in assigned:
                with span("controller.upload.transition", phase=ServerQueryPhase.SEGMENT_UPLOAD_TRANSITION, role="controller", server=sid):
                    if self._transitions is not None:
                        try:
                            self.add_to_server(handles[sid], table, name, seg_dir, config)
                            self._transitions.record_external_view(table, name, sid, "ONLINE")
                        except Exception as e:  # pinotlint: disable=deadline-swallow — segment-add control plane; failure enqueues a retryable helix transition
                            _LOG.warning("%s of %s not confirmed by %s (%s): queued for redelivery", name, table, sid, e)
                            self._transitions.enqueue(table, name, sid, "add", str(seg_dir))
                            if isinstance(e, ServerTimedOut):
                                at_work.append(sid)
                    else:
                        self.add_to_server(handles[sid], table, name, seg_dir, config)
            # A server that is down is the queue's to bring back, and the upload is acknowledged without it. One that
            # took the call and was slow is still loading: it would host the segment while the view, which brokers
            # route by, lacked it, and whoever took the acknowledgement for "loaded" was refused its first query
            # (PERF.md, PR 35). So the acknowledgement waits, a transition's time at most, for the queue's delivery.
            deadline = time.time() + self.TRANSITION_CONFIRM_S
            while at_work and time.time() < deadline:
                view = (self.external_view(table) or {}).get(name, {})
                at_work = [sid for sid in at_work if view.get(sid) != "ONLINE"]
                if at_work:
                    time.sleep(0.05)
        finally:
            self._loading.difference_update(loading)
        return assigned

    def dim_table_spec(self, table: str, config: TableConfig | None = None) -> dict | None:
        """`{"primaryKeyColumns": [...]}` of a table whose config flags it
        isDimTable, else None: what a server is told with every segment of
        such a table, so that it keeps the table's manager itself
        (DimensionTableDataManager lives on the servers)."""
        config = config or self.get_table(table)
        if config is None or not (config.extra or {}).get("isDimTable"):
            return None
        schema = self.get_schema(table)
        keys = list(schema.primary_key_columns) if schema else []
        if not keys:
            raise ValueError(f"dimension table {table!r} needs primaryKeyColumns in its schema")
        return {"primaryKeyColumns": keys}

    def add_to_server(self, handle, table: str, segment_name: str, seg_dir, config: TableConfig | None = None) -> None:
        """The OFFLINE -> ONLINE transition of one replica, as every path that
        delivers one makes it (upload, the transition queue, a rebalance)."""
        spec = self.dim_table_spec(table, config)
        if spec is None:
            handle.add_segment(table, segment_name, str(seg_dir))
        else:
            handle.add_segment(table, segment_name, str(seg_dir), dim_table=spec)

    @staticmethod
    def _compute_partitions(segment: ImmutableSegment, config: TableConfig) -> dict:
        """Per-segment partition metadata (SegmentPartitionConfig parity):
        for each declared partition column, the set of partition ids present —
        the broker's MultiPartitionColumnsSegmentPruner consumes this."""
        ppc = (config.extra or {}).get("segmentPartitionConfig") or {}
        out = {}
        for col, n_parts in ppc.items():
            ci = segment.columns.get(col)
            if ci is None:
                continue
            from pinot_tpu.cluster.routing import partition_of

            if ci.dictionary is not None:
                distinct = ci.dictionary.values
            else:
                import numpy as np

                distinct = np.unique(ci.forward)
                if len(distinct) > 100_000:  # unpartitioned high-cardinality raw column
                    continue
            ids = sorted({partition_of(v, int(n_parts)) for v in distinct.tolist()})
            out[col] = {"numPartitions": int(n_parts), "partitionIds": ids}
        return out

    def _assign(self, table: str, replication: int) -> list[str]:
        """Balanced assignment restricted to the table's server-tenant pool:
        pick the `replication` eligible servers hosting the fewest segments
        of this table (OfflineSegmentAssignment + tenant tags). The caller
        holds `_assign_lock` until the choice stands in the ideal state."""
        from pinot_tpu.cluster.tenancy import candidate_servers

        handles = self.servers()
        if not handles:
            raise RuntimeError("no servers registered")
        config = self.get_table(table)
        eligible = set(candidate_servers(self, config)) if config is not None else set(handles)
        handles = {sid: h for sid, h in handles.items() if sid in eligible}
        if not handles:
            raise RuntimeError(f"no servers in table {table!r}'s tenant")
        ideal = self.store.get(f"/tables/{table}/idealstate") or {}
        load: dict[str, int] = {sid: 0 for sid in handles}
        for seg, replicas in ideal.items():
            for sid in replicas:
                if sid in load:
                    load[sid] += 1
        ranked = sorted(load, key=lambda s: (load[s], s))
        return ranked[: max(1, min(replication, len(ranked)))]

    def delete_segment(self, table: str, segment_name: str, remove_from_deep_store: bool = True) -> None:
        """Drop a segment: server unload transitions, ideal-state removal,
        metadata + deep-store cleanup (SegmentDeletionManager parity). Any
        queued ADD transitions for the segment are cancelled and its
        external-view entry cleared — a surviving add would otherwise retry
        forever against a deleted deep-store dir, or resurrect the segment."""
        # order matters: drop the ideal-state intent FIRST so the reconciler
        # and the delivery worker's obsolete-message guard both stop wanting
        # the segment, THEN cancel queued messages, then unload
        replicas: dict = {}

        def drop(ideal: dict | None) -> dict:
            ideal = ideal or {}
            replicas.update(ideal.pop(segment_name, {}))
            return ideal

        self._update_ideal_state(table, drop)
        if self._transitions is not None:
            self._transitions.cancel(table, segment_name)
        handles = self.servers()
        for sid in replicas:
            srv = handles.get(sid)
            if srv is not None:
                srv.remove_segment(table, segment_name)
        meta = self.store.get(f"/tables/{table}/segments/{segment_name}")
        self.store.delete(
            f"/tables/{table}/segments/{segment_name}", fence=self.lease_fence(), bump=routing_version_path(table)
        )
        if remove_from_deep_store and meta and meta.get("location"):
            import shutil

            shutil.rmtree(meta["location"], ignore_errors=True)

    def reload_segments(self, table: str, segment_name: str | None = None) -> list[str]:
        """Rebuild segments from deep-store data under the CURRENT table
        config/schema (segment reload REST + SegmentPreProcessor parity:
        index config changes take effect on reload). Preserves realtime
        offset metadata across the rebuild."""
        from pinot_tpu.segment.builder import SegmentBuilder
        from pinot_tpu.segment.loader import load_segment

        schema = self.get_schema(table)
        config = self.get_table(table)
        if schema is None or config is None:
            raise KeyError(f"no such table: {table}")
        builder = SegmentBuilder(schema, config)
        reloaded = []
        for name, meta in sorted(self.all_segment_metadata(table).items()):
            if segment_name is not None and name != segment_name:
                continue
            loc = meta.get("location")
            if not loc:
                continue
            seg = load_segment(loc)
            cols = {c: ci.materialize() for c, ci in seg.columns.items()}
            rebuilt = builder.build(cols, name)
            keep = {k: v for k, v in meta.items() if k in ("startOffset", "endOffset", "partition", "refreshEpoch")}
            self.delete_segment(table, name)
            self.upload_segment(table, rebuilt)
            if keep:
                new_meta = self.segment_metadata(table, name) or {}
                new_meta.update(keep)
                self.write_segment_metadata(table, name, new_meta)
            reloaded.append(name)
        return reloaded

    def replace_segments(self, table: str, old_names: list[str], new_segments: list[ImmutableSegment]) -> None:
        """Atomic-enough swap (segment-lineage startReplaceSegments/
        endReplaceSegments parity): upload replacements first, then drop the
        originals, so readers always see a complete data set. Under HA, a
        replacement whose ADD was only queued (server transiently down) must
        come ONLINE before the originals are dropped — deleting early would
        leave readers seeing neither old nor new rows."""
        for seg in new_segments:
            self.upload_segment(table, seg)
        if self._transitions is not None:
            if not self._transitions.await_online(
                table, [s.name for s in new_segments], timeout=30.0
            ):
                raise RuntimeError(
                    f"replacement segments for {table!r} did not come ONLINE; "
                    "originals kept (swap aborted, retry when servers recover)"
                )
        for name in old_names:
            self.delete_segment(table, name)

    # -- realtime segment state (LLC CONSUMING entries) ----------------------

    def set_segment_state(self, table: str, segment: str, server_id: str, state: str | None) -> None:
        """Set/remove one (segment, server) ideal-state entry; state=None
        removes the segment entry entirely when its replica map empties."""

        def change(ideal: dict | None) -> dict:
            ideal = ideal or {}
            entry = ideal.get(segment, {})
            if state is None:
                entry.pop(server_id, None)
            else:
                entry[server_id] = state
            if entry:
                ideal[segment] = entry
            else:
                ideal.pop(segment, None)
            return ideal

        self._update_ideal_state(table, change)
        if self._transitions is not None:
            # both callers speak for a server that holds the replica already (a rebalance move after
            # its `add_segment`, a consuming segment as it opens): the view follows in the same call
            self._transitions.record_external_view(table, segment, server_id, state)

    def _update_external_view(self, table: str, fn) -> dict | None:
        """The one way an external view changes; brokers route by it, so the
        routing version moves with the write, as with the ideal state's."""
        return self.store.update(
            f"/tables/{table}/externalview", fn, fence=self.lease_fence(), bump=routing_version_path(table)
        )

    def external_view(self, table: str) -> dict | None:
        """What the servers have confirmed of `table`: {segment: {server:
        state}}. None where no view is kept: without HA a state transition is
        a synchronous call onto the server, and the view equals the ideal
        state once it returns (module docstring)."""
        if self._transitions is None:
            return None
        return self.store.get(f"/tables/{table}/externalview") or {}

    def _update_ideal_state(self, table: str, fn) -> None:
        """The one way an ideal state changes: the write and the routing
        version's move are one step of the store (with the move as a call of
        its own after the write, a broker asking in between is told
        "unchanged" about an ideal state that has changed)."""
        self.store.update(
            f"/tables/{table}/idealstate", fn, fence=self.lease_fence(), bump=routing_version_path(table)
        )

    def write_segment_metadata(self, table: str, segment: str, meta: dict) -> None:
        """Write one segment's metadata document; the routing version moves
        with it (fenced: a writer outliving this controller's lease must not
        overwrite what the new lead has since written)."""
        self.store.set(
            f"/tables/{table}/segments/{segment}", meta, fence=self.lease_fence(), bump=routing_version_path(table)
        )

    # -- views ---------------------------------------------------------------

    def reset_external_views(self) -> int:
        """Disaster-recovery entry point for a full-cluster cold restart:
        external views record what servers held LAST session, and in the
        reference they are derived from session-ephemeral Helix current
        state — a restarted cluster must not trust them. Clearing them makes
        the reconciler re-enqueue every (segment, replica) the ideal state
        wants, and restarted servers re-download CRC-verified copies from
        the deep store. Returns how many view docs were cleared."""
        n = 0
        for t in self.tables():
            if self.store.get(f"/tables/{t}/externalview") is not None:
                self.store.delete(f"/tables/{t}/externalview", fence=self.lease_fence(), bump=routing_version_path(t))
                n += 1
        return n

    def ideal_state(self, table: str) -> dict:
        return self.store.get(f"/tables/{table}/idealstate") or {}

    def segment_metadata(self, table: str, segment: str) -> dict | None:
        return self.store.get(f"/tables/{table}/segments/{segment}")

    def all_segment_metadata(self, table: str) -> dict[str, dict]:
        out = {}
        for p in self.store.list(f"/tables/{table}/segments/"):
            name = p.split("/")[-1]
            out[name] = self.store.get(p)
        return out
