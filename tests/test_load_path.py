"""The segment load path: an HTTP upload lands in the deep store as it was
sent, concurrent uploads keep every segment and spread a table evenly, a bad
upload leaves nothing behind, and the lead lease is renewed past the store's
writes (ISSUE 27; PERF.md, PR 27)."""

import io
import json
import sys
import tarfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pinot_tpu.cluster import Controller, PropertyStore, Server
from pinot_tpu.cluster.ha import LeaderElection
from pinot_tpu.cluster.http import ControllerHTTPService
from pinot_tpu.cluster.metadata import LEASE_PATH, FencedWriteError
from pinot_tpu.common import DataType, Schema, TableConfig
from pinot_tpu.common.errors import SegmentUploadError
from pinot_tpu.segment import SegmentBuilder
from pinot_tpu.segment.builder import write_segment
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.segment.store import SEGMENT_FILE, segment_file_crc

TABLE = "orders"


def _schema(name=TABLE):
    return Schema.build(
        name,
        dimensions=[("region", DataType.STRING), ("day", DataType.INT), ("tag", DataType.BYTES)],
        metrics=[("amount", DataType.LONG), ("price", DataType.DOUBLE)],
    )


def _segment(schema, name, seed=7, n=500):
    rng = np.random.default_rng(seed)
    data = {
        "region": np.array(["EU", "US", "APAC", "LATAM"], dtype=object)[rng.integers(0, 4, n)],
        "day": rng.integers(19920101, 19981231, n).astype(np.int32),
        "tag": np.array([b"\x00a", b"b\x00", b"\xffc"], dtype=object)[rng.integers(0, 3, n)],
        "amount": rng.integers(1, 1000, n).astype(np.int64),
        "price": rng.random(n) * 1e4,
    }
    return SegmentBuilder(schema).build(data, name)


def _archive(seg_dir) -> bytes:
    """What a client posts: the segment directory as a gzipped tar."""
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w:gz", compresslevel=1) as tf:
        tf.add(seg_dir, arcname=seg_dir.name)
    return buf.getvalue()


def _controller(tmp_path, n_servers=1, ha=False, tables=(TABLE,)):
    controller = Controller(PropertyStore(tmp_path / "zk"), tmp_path / "deep")
    if ha:
        controller.enable_ha()
    for i in range(n_servers):
        controller.register_server(f"server_{i}", Server(f"server_{i}"))
    for t in tables:
        controller.add_schema(_schema(t))
        controller.add_table(TableConfig(t, replication=1))
    return controller


def _post(url, body: bytes):
    req = urllib.request.Request(url, data=body, headers={"Content-Type": "application/gzip"})
    with urllib.request.urlopen(req, timeout=60) as rsp:
        return json.loads(rsp.read())


# ---------------------------------------------------------------------------
# concurrent uploads
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("entry", ["archive", "in-process"])
def test_concurrent_uploads_keep_every_segment_and_spread_a_table_evenly(tmp_path, entry):
    """N threads, N segments, one controller with HA on, four servers: the ideal
    state names all N (a lost update dropped one: seed 3260000704, PERF.md PR 26),
    every server gets its equal share without help, no write is fenced."""
    n = 12
    controller = _controller(tmp_path, n_servers=4, ha=True)
    schema = _schema()
    segments = [_segment(schema, f"{TABLE}_{i}", seed=i, n=50) for i in range(n)]
    if entry == "archive":
        jobs = [_archive(write_segment(s, tmp_path / "built")) for s in segments]
        upload = lambda job: controller.upload_segment_archive(TABLE, job)  # noqa: E731
    else:
        jobs = segments
        upload = lambda job: controller.upload_segment(TABLE, job)  # noqa: E731
    errors = []
    start = threading.Barrier(n)

    def worker(job):
        start.wait(timeout=30)
        try:
            upload(job)
        except Exception as e:  # noqa: BLE001 — any failure fails the test, by name
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(job,)) for job in jobs]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # more interleavings between an ideal state's read and its write
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
        controller.stop_ha()
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors  # a FencedWriteError among them: the lease ran out under the uploads
    ideal = controller.ideal_state(TABLE)
    assert sorted(ideal) == sorted(s.name for s in segments)
    hosted = {sid: sorted(srv._tables.get(TABLE, {})) for sid, srv in controller.servers().items()}
    assert {sid: len(segs) for sid, segs in hosted.items()} == {f"server_{i}": n // 4 for i in range(4)}
    # the ideal state is what the servers hold, and the metadata's `servers` what the ideal state says
    for name, replicas in ideal.items():
        (sid,) = replicas
        assert name in hosted[sid]
        assert controller.segment_metadata(TABLE, name)["servers"] == [sid]
    assert sorted(controller.all_segment_metadata(TABLE)) == sorted(ideal)


# ---------------------------------------------------------------------------
# an upload that fails leaves nothing
# ---------------------------------------------------------------------------


def _flipped(seg_dir) -> bytes:
    raw = bytearray((seg_dir / SEGMENT_FILE).read_bytes())
    raw[len(raw) // 2] ^= 0x10
    (seg_dir / SEGMENT_FILE).write_bytes(bytes(raw))  # deliberately torn-unsafe: simulating damage in flight
    return _archive(seg_dir)


@pytest.mark.parametrize("damage", ["flipped-bit", "cut-archive", "no-segment-file-name"])
@pytest.mark.parametrize("first", [True, False], ids=["first-segment", "later-segment"])
def test_a_bad_upload_leaves_no_directory_and_no_metadata(tmp_path, damage, first):
    controller = _controller(tmp_path)
    schema = _schema()
    if not first:
        controller.upload_segment(TABLE, _segment(schema, f"{TABLE}_good"))
    before = sorted(p.name for p in (tmp_path / "deep").rglob("*"))
    seg_dir = write_segment(_segment(schema, f"{TABLE}_0"), tmp_path / "built")
    if damage == "flipped-bit":
        body = _flipped(seg_dir)
    elif damage == "cut-archive":
        body = _archive(seg_dir)[:-200]
    else:  # a name that would leave the table's directory
        seg = _segment(schema, "../escaped")
        seg_dir = tmp_path / "built" / "x"
        from pinot_tpu.segment.store import write_segment_file

        write_segment_file(seg, seg_dir)
        body = _archive(seg_dir)
    with pytest.raises(SegmentUploadError):
        controller.upload_segment_archive(TABLE, body)
    assert sorted(p.name for p in (tmp_path / "deep").rglob("*")) == before  # no segment dir, no `.upload-*`
    assert not (tmp_path / "escaped").exists()
    assert controller.segment_metadata(TABLE, f"{TABLE}_0") is None
    assert sorted(controller.ideal_state(TABLE)) == ([] if first else [f"{TABLE}_good"])
    # over HTTP the same failure is typed, and the sound bytes go through afterwards
    svc = ControllerHTTPService(controller, port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"http://127.0.0.1:{svc.port}/segments/{TABLE}", body)
        assert "SegmentUploadError" in ei.value.read().decode()
        sound = _archive(write_segment(_segment(schema, f"{TABLE}_0"), tmp_path / "built2"))
        assert _post(f"http://127.0.0.1:{svc.port}/segments/{TABLE}", sound)["segment"] == f"{TABLE}_0"
    finally:
        svc.stop()
    assert f"{TABLE}_0" in controller.ideal_state(TABLE)


# ---------------------------------------------------------------------------
# the deep store holds what was sent
# ---------------------------------------------------------------------------


def _same_segment(a, b):
    assert a.name == b.name and a.n_docs == b.n_docs and list(a.columns) == list(b.columns)
    for col, ca in a.columns.items():
        cb = b.columns[col]
        assert ca.data_type == cb.data_type and ca.stats == cb.stats, col
        assert np.array_equal(ca.forward, cb.forward), col
        assert (ca.dictionary is None) == (cb.dictionary is None), col
        if ca.dictionary is not None:
            assert list(ca.dictionary.values) == list(cb.dictionary.values), col


def test_an_http_upload_lands_as_it_was_sent_and_reads_as_the_decoded_path_wrote_it(tmp_path):
    """The deep-store file is byte for byte what the client sent; it loads to
    the segment that the old path (decode, encode again, write) stored, and the
    controller records the same metadata for it."""
    controller = _controller(tmp_path, tables=(TABLE, "orders_old"))
    schema = _schema()
    seg = _segment(schema, f"{TABLE}_0", n=2000)
    seg_dir = write_segment(seg, tmp_path / "built")
    sent = (seg_dir / SEGMENT_FILE).read_bytes()
    svc = ControllerHTTPService(controller, port=0)
    try:
        answer = _post(f"http://127.0.0.1:{svc.port}/segments/{TABLE}", _archive(seg_dir))
    finally:
        svc.stop()
    assert answer == {"status": "ok", "segment": f"{TABLE}_0", "servers": ["server_0"]}
    landed = tmp_path / "deep" / TABLE / f"{TABLE}_0"
    assert [p.name for p in (tmp_path / "deep" / TABLE).iterdir()] == [f"{TABLE}_0"]  # no temporary directory stays
    assert (landed / SEGMENT_FILE).read_bytes() == sent
    # the old path: what the client sent, decoded, and written by the controller
    controller.upload_segment("orders_old", load_segment(seg_dir))
    old = tmp_path / "deep" / "orders_old" / f"{TABLE}_0"
    _same_segment(load_segment(landed), load_segment(old))
    _same_segment(load_segment(landed), seg)
    new_meta, old_meta = controller.segment_metadata(TABLE, f"{TABLE}_0"), controller.segment_metadata("orders_old", f"{TABLE}_0")
    assert new_meta["fileCrc"] == old_meta["fileCrc"] == segment_file_crc(landed)
    assert new_meta["stats"] == old_meta["stats"] and new_meta["numDocs"] == old_meta["numDocs"] == 2000
    assert new_meta["stats"]["region"] == {"min": "APAC", "max": "US", "cardinality": 4}
    assert new_meta["location"] == str(landed)
    # the server serves the landed copy
    (served,) = controller.servers()["server_0"]._tables[TABLE].values()
    _same_segment(served, seg)


def test_a_second_upload_of_a_segment_replaces_its_file(tmp_path):
    controller = _controller(tmp_path)
    schema = _schema()
    for seed in (1, 2):
        seg_dir = write_segment(_segment(schema, f"{TABLE}_0", seed=seed), tmp_path / f"built{seed}")
        controller.upload_segment_archive(TABLE, _archive(seg_dir))
        landed = tmp_path / "deep" / TABLE / f"{TABLE}_0" / SEGMENT_FILE
        assert landed.read_bytes() == (seg_dir / SEGMENT_FILE).read_bytes()
        assert controller.segment_metadata(TABLE, f"{TABLE}_0")["fileCrc"] == segment_file_crc(landed)
    assert list(controller.ideal_state(TABLE)) == [f"{TABLE}_0"]
    assert [p.name for p in (tmp_path / "deep" / TABLE).iterdir()] == [f"{TABLE}_0"]


def test_a_partitioned_table_still_has_its_uploads_decoded(tmp_path):
    controller = Controller(PropertyStore(), tmp_path / "deep")
    controller.register_server("server_0", Server("server_0"))
    controller.add_schema(_schema())
    controller.add_table(TableConfig(TABLE, replication=1, extra={"segmentPartitionConfig": {"region": 4}}))
    seg = _segment(_schema(), f"{TABLE}_0")
    controller.upload_segment_archive(TABLE, _archive(write_segment(seg, tmp_path / "built")))
    controller.add_schema(_schema("orders_old"))
    controller.add_table(TableConfig("orders_old", replication=1, extra={"segmentPartitionConfig": {"region": 4}}))
    controller.upload_segment("orders_old", seg)
    partitions = controller.segment_metadata(TABLE, f"{TABLE}_0")["partitions"]
    assert partitions == controller.segment_metadata("orders_old", f"{TABLE}_0")["partitions"]
    assert partitions["region"]["numPartitions"] == 4 and partitions["region"]["partitionIds"]


# ---------------------------------------------------------------------------
# the lease
# ---------------------------------------------------------------------------


def test_a_renewal_does_not_wait_for_the_stores_writes(tmp_path):
    """Five 181 MB uploads at a time held the renewal behind their writes'
    fsyncs until the 2 s lease ran out (seed 3260000902; PERF.md, PR 26). A
    renewal changes no epoch, so it takes the lease's own section only."""
    store = PropertyStore(tmp_path / "zk")
    election = LeaderElection(store, "c1", ttl=0.6, renew_every=0.1)
    election.start()
    try:
        assert election.is_leader and election.epoch == 1
        with store._exclusive():  # a store write that takes two leases' time
            time.sleep(1.3)
            assert election.is_leader and election.epoch == 1
            assert store.get_versioned(LEASE_PATH)[0]["expires"] > time.time()
        store.set("/tables/t/config", {"x": 1}, fence=election.epoch)  # and no write of the leader's is fenced
    finally:
        election.stop()


def test_a_reclaim_raises_the_controllers_own_epoch_before_the_store_shows_it(tmp_path):
    """When its lease did run out, the controller claims it again at the next
    epoch. Its own copy changes inside the store's update: a write of its own
    that comes next carries the new epoch and is not fenced by its own claim."""
    seen = []

    class Watching(PropertyStore):
        def _write(self, path, doc, version):
            if path == LEASE_PATH:
                seen.append((doc["epoch"], election.epoch))
            super()._write(path, doc, version)

    store = Watching(tmp_path / "zk")
    election = LeaderElection(store, "c1", ttl=5.0, renew_every=0.1)
    election._tick()
    store.update(LEASE_PATH, lambda d: {**d, "expires": 0.0})  # the lease ran out under its holder
    election._tick()
    assert election.is_leader and election.epoch == 2
    assert [(in_store, own) for in_store, own in seen if in_store != own] == []
    assert (2, 2) in seen
    store.set("/tables/t/config", {"x": 1}, fence=election.epoch)
    with pytest.raises(FencedWriteError):
        store.set("/tables/t/config", {"x": 2}, fence=1)


def test_an_archive_in_the_v1_layout_is_decoded_and_written_as_the_deep_store_writes(tmp_path):
    """metadata.json + columns.npz is not the deep store's format: such an
    upload takes the decoding entry, and lands as a `.ptseg` all the same."""
    controller = _controller(tmp_path)
    seg = _segment(_schema(), f"{TABLE}_0")
    name, assigned = controller.upload_segment_archive(TABLE, _archive(write_segment(seg, tmp_path / "built", fmt="npz")))
    assert (name, assigned) == (f"{TABLE}_0", ["server_0"])
    assert [p.name for p in (tmp_path / "deep" / TABLE).iterdir()] == [f"{TABLE}_0"]
    _same_segment(load_segment(tmp_path / "deep" / TABLE / f"{TABLE}_0"), seg)
    assert (tmp_path / "deep" / TABLE / f"{TABLE}_0" / SEGMENT_FILE).exists()
