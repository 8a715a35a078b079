"""Single-file segment store: all column/index data in one `segment.ptseg`.

Reference parity: Pinot V3 segment format — one `columns.psf` with an index
map of (column, indexType) -> (offset, size) entries plus
`metadata.properties` (SegmentDirectory / SingleFileIndexDirectory.java:88),
with the segment CRC recorded in ZK metadata and validated on load/download
(ImmutableSegmentLoader + SegmentFetcher retry tier). Here: one file holding
back-to-back encoded entries, a JSON index map at the tail, and a fixed
footer. Integrity is two-level: a per-entry CRC32 (checked lazily on each
entry decode) pinpoints WHICH index is damaged, and a whole-file CRC32 in
the v03 footer — covering every byte before the footer: header magic, entry
blobs, and index JSON — is checked once at open and is what the controller
records in the segment's ZK metadata (`fileCrc`) at upload/commit time, so
a downloader or the integrity scrubber can verify a copy against cluster
truth without trusting the file's own footer. Any mismatch raises the typed
`SegmentCorruptedError` (code SEGMENT_CORRUPTED), which the server's
self-healing path catches to quarantine + re-fetch. Writes are
crash-consistent: `finish` funnels the whole image through
`common/durability.py` (tmp + fsync + rename), so a torn segment file can
only ever be a tmp sibling. Dict-id forward indexes are fixed-bit packed
and chunks are LZ4-compressed via the native C++ kernels (pinot_tpu/native)
exactly where the reference leans on FixedBitSVForwardIndexReaderV2 +
ChunkCompressionType.LZ4.

Layout (v03, written by this module):
    magic "PTSEGv03"
    entry blobs (back-to-back, 8-byte aligned)
    index-map JSON (utf-8)
    footer: uint64 index_off, uint64 index_len,
            uint32 file_crc (CRC32 of all preceding bytes), magic "PTSEGv03"

Legacy v02 files (24-byte footer, no whole-file CRC) still load; they get
structural + per-entry verification only.

Entry kinds:
    arr  — numeric ndarray: dtype + shape, codec raw|lz4
    ids  — int32 dict ids fixed-bit packed into uint64 words, codec raw|lz4
    str  — var-length strings/bytes: int32 length array entry + blob entry
"""

from __future__ import annotations

import json
import mmap
from pathlib import Path

import numpy as np

from pinot_tpu import native
from pinot_tpu.common.durability import atomic_write_bytes
from pinot_tpu.common.errors import SegmentCorruptedError
from pinot_tpu.common.faults import FAULTS

MAGIC = b"PTSEGv03"
MAGIC_V2 = b"PTSEGv02"
SEGMENT_FILE = "segment.ptseg"
#: v03 footer: u64 index_off + u64 index_len + u32 file_crc + 8-byte magic
FOOTER_V3 = 8 + 8 + 4 + len(MAGIC)


import os


def default_chunk_codec() -> str:
    """Segment chunk codec (ChunkCompressionType parity): lz4 (default),
    zstd, gzip, snappy, or raw — via PINOT_TPU_CHUNK_CODEC or per-writer."""
    return os.environ.get("PINOT_TPU_CHUNK_CODEC", "lz4")


def _maybe_compress(raw: bytes, codec: str) -> tuple[str, bytes]:
    """Compress with the requested codec when available and it actually
    helps, else raw."""
    if codec != "raw" and native.codec_available(codec) and len(raw) >= 64:
        comp = native.chunk_compress(raw, codec)
        if len(comp) < len(raw) * 0.9:
            return codec, comp
    return "raw", raw


class SegmentFileWriter:
    def __init__(self, codec: str | None = None):
        self._blobs: list[bytes] = []
        self._entries: dict[str, dict] = {}
        self._pos = len(MAGIC)
        self._codec = codec or default_chunk_codec()

    def _add(self, key: str, kind: str, raw: bytes, **meta) -> None:
        codec, stored = _maybe_compress(raw, self._codec)
        pad = (-self._pos) % 8
        self._blobs.append(b"\x00" * pad + stored)
        self._pos += pad
        self._entries[key] = {
            "kind": kind,
            "off": self._pos,
            "stored": len(stored),
            "raw": len(raw),
            "codec": codec,
            "crc": native.crc32(raw),
            **meta,
        }
        self._pos += len(stored)

    def write_array(self, key: str, arr: np.ndarray) -> None:
        arr = np.ascontiguousarray(arr)
        self._add(key, "arr", arr.tobytes(), dtype=arr.dtype.str, shape=list(arr.shape))

    def write_ids(self, key: str, ids: np.ndarray, cardinality: int) -> None:
        bits = native.bits_needed(cardinality)
        packed = native.bitpack(ids, bits)
        self._add(key, "ids", packed.tobytes(), bits=bits, n=len(ids))

    def write_strings(self, key: str, values: np.ndarray, is_bytes: bool) -> None:
        encoded = [v if is_bytes else str(v).encode("utf-8") for v in values]
        lens = np.asarray([len(b) for b in encoded], dtype=np.int32)
        self.write_array(key + "~len", lens)
        self._add(key, "str", b"".join(encoded), bytes=is_bytes, n=len(values))

    def finish(self, path: Path, meta: dict) -> None:
        meta = dict(meta)
        meta["entries"] = self._entries
        index = json.dumps(meta).encode("utf-8")
        index_off = self._pos
        parts = [MAGIC, *self._blobs, index]
        file_crc = 0
        for part in parts:  # the CRC runs over the parts, and the image is put together once
            file_crc = native.crc32(part, file_crc)
        parts.append(np.asarray([index_off, len(index)], dtype="<u8").tobytes())
        parts.append(np.asarray([file_crc], dtype="<u4").tobytes())
        parts.append(MAGIC)
        image = b"".join(parts)
        # tmp + fsync + rename: a crash mid-write leaves no torn .ptseg
        atomic_write_bytes(path, image)


def write_segment_file(seg, seg_dir: Path) -> Path:
    """Serialize an ImmutableSegment (including star-trees and aux indexes)."""
    from pinot_tpu.common.types import DataType

    w = SegmentFileWriter()
    col_meta = []
    for col, ci in seg.columns.items():
        if ci.dictionary is not None:
            w.write_ids(f"fwd::{col}", ci.forward, ci.dictionary.cardinality)
            dv = ci.dictionary.values
            if ci.data_type == DataType.BYTES:
                w.write_strings(f"dict::{col}", dv, is_bytes=True)
            elif ci.data_type in (DataType.STRING, DataType.JSON):
                w.write_strings(f"dict::{col}", dv, is_bytes=False)
            else:
                w.write_array(f"dict::{col}", dv)
        else:
            w.write_array(f"fwd::{col}", ci.forward)
        if ci.lens is not None:
            w.write_array(f"mvlens::{col}", ci.lens)
        col_meta.append(
            {
                "name": col,
                "encoding": "DICT" if ci.dictionary is not None else "RAW",
                "stats": ci.stats.to_dict(),
                **({"mv": True} if ci.lens is not None else {}),
            }
        )
    star_meta = []
    for i, st in enumerate(seg.extras.get("startree", [])):
        for k, arr in st.arrays.items():
            w.write_array(f"star{i}::{k}", arr)
        star_meta.append(
            {"dimensions": st.dimensions, "pairs": st.function_column_pairs, "nRows": st.n_rows}
        )
    aux_meta: dict = {"bloom": {}, "inverted": [], "range": []}
    for col, bf in seg.extras.get("bloom", {}).items():
        w.write_array(f"bloom::{col}", bf.bits)
        aux_meta["bloom"][col] = bf.n_hashes
    for col, inv in seg.extras.get("inverted", {}).items():
        w.write_array(f"inv_off::{col}", inv.offsets)
        w.write_array(f"inv_doc::{col}", inv.doc_ids)
        aux_meta["inverted"].append(col)
    for col, ri in seg.extras.get("range", {}).items():
        w.write_array(f"range_doc::{col}", ri.sorted_doc_ids)
        w.write_array(f"range_val::{col}", ri.sorted_values)
        aux_meta["range"].append(col)
    for col, ti in seg.extras.get("text", {}).items():
        w.write_strings(f"text_vocab::{col}", ti.vocab, is_bytes=False)
        w.write_array(f"text_off::{col}", ti.offsets)
        w.write_array(f"text_doc::{col}", ti.doc_ids)
        aux_meta.setdefault("text", []).append(col)
    for col, ji in seg.extras.get("json", {}).items():
        w.write_strings(f"json_keys::{col}", ji.keys, is_bytes=False)
        w.write_array(f"json_off::{col}", ji.offsets)
        w.write_array(f"json_doc::{col}", ji.doc_ids)
        aux_meta.setdefault("json", []).append(col)
    for key, gi in seg.extras.get("geo", {}).items():
        w.write_array(f"geo_cells::{key}", gi.cells)
        w.write_array(f"geo_off::{key}", gi.offsets)
        w.write_array(f"geo_doc::{key}", gi.doc_ids)
        if hasattr(gi, "res_deg"):
            aux_meta.setdefault("geo", {})[key] = {"resDeg": gi.res_deg, "bbox": list(gi.bbox)}
        else:  # H3Index (hex cells)
            aux_meta.setdefault("geo", {})[key] = {
                "kind": "h3",
                "res": gi.res,
                "bbox": list(gi.bbox),
                "maxCellRadiusM": gi.max_cell_radius_m,
            }
    for col, vi in seg.extras.get("vector", {}).items():
        w.write_array(f"vector::{col}", vi.vectors)
        # HNSW graphs rebuild deterministically on load (SegmentPreProcessor
        # on-load index build parity); only the vectors persist
        aux_meta.setdefault("vector", {})[col] = type(vi).__name__
    for col in seg.extras.get("fst", {}):
        aux_meta.setdefault("fst", []).append(col)  # rebuilt from the dictionary
    for col in seg.extras.get("map", {}):
        aux_meta.setdefault("map", []).append(col)  # rebuilt from the column
    if seg.extras.get("__custom_indexes__"):
        # plugin indexes rebuild on load via the SPI registry
        aux_meta["custom"] = seg.extras["__custom_indexes__"]
    for col, bm in seg.extras.get("null", {}).items():
        w.write_array(f"null::{col}", bm)
        aux_meta.setdefault("null", []).append(col)
    meta = {
        "formatVersion": 2,
        "segmentName": seg.name,
        "numDocs": seg.n_docs,
        "schema": json.loads(seg.schema.to_json()),
        "columns": col_meta,
        "starTrees": star_meta,
        "auxIndexes": aux_meta,
    }
    seg_dir.mkdir(parents=True, exist_ok=True)
    out = seg_dir / SEGMENT_FILE
    w.finish(out, meta)
    return seg_dir


class SegmentFileReader:
    """Reads a .ptseg file; entries decode lazily on access. The v03
    whole-file CRC is verified once at open (`verify=False` skips it for
    callers that already checked the bytes against ZK metadata); structural
    or CRC damage raises the typed SegmentCorruptedError."""

    def __init__(self, path: Path, verify: bool = True):
        self.path = Path(path)
        raw = FAULTS.maybe_fail("storage.read", _map_file(self.path))
        self.file_crc, index = _footer(raw, str(path))
        if verify and self.file_crc is not None and native.crc32(memoryview(raw)[:-FOOTER_V3]) != self.file_crc:
            raise SegmentCorruptedError(f"{path}: whole-file CRC mismatch", path=str(path))
        self._buf = np.frombuffer(raw, dtype=np.uint8)
        self.meta = _index_map(raw, index, str(path))
        self.entries = self.meta["entries"]

    def _raw_bytes(self, e: dict) -> bytes:
        stored = self._buf[e["off"] : e["off"] + e["stored"]].tobytes()
        raw = native.chunk_decompress(stored, e["raw"], e["codec"])
        if native.crc32(raw) != e["crc"]:
            raise SegmentCorruptedError(
                f"{self.path}: CRC mismatch on entry", path=str(self.path)
            )
        return raw

    def keys(self):
        return self.entries.keys()

    def __contains__(self, key: str) -> bool:
        return key in self.entries

    def read(self, key: str) -> np.ndarray:
        e = self.entries[key]
        raw = self._raw_bytes(e)
        if e["kind"] == "arr":
            return np.frombuffer(raw, dtype=np.dtype(e["dtype"])).reshape(e["shape"]).copy()
        if e["kind"] == "ids":
            words = np.frombuffer(raw, dtype=np.uint64)
            # dict ids lie below 2**31: the unpacked uint32s are the int32s, bit for bit (a copy cost
            # 80 ms a 4M-row column, a third of a segment's load)
            return native.bitunpack(words, e["n"], e["bits"]).view(np.int32)
        if e["kind"] == "str":
            lens = self.read(key + "~len")
            out = np.empty(e["n"], dtype=object)
            pos = 0
            if e["bytes"]:
                for i, l in enumerate(lens):
                    out[i] = raw[pos : pos + l]
                    pos += l
            else:
                for i, l in enumerate(lens):
                    out[i] = raw[pos : pos + l].decode("utf-8")
                    pos += l
            return out
        raise AssertionError(e["kind"])


def _map_file(path: Path):
    """The file's bytes as a read-only memory map: a verification or a load
    reads a 181 MB segment file out of the page cache where it lies, with no
    copy of it made first. Writers replace a segment file by rename and never
    in place, so a map stays whole for as long as it is held."""
    with open(path, "rb") as f:
        try:
            return mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        except ValueError:  # an empty file cannot be mapped
            return b""


def _footer(raw: bytes, label: str) -> tuple[int | None, tuple[int, int]]:
    """(whole-file CRC stored in a v03 footer, None for legacy v02; (offset,
    length) of the index map) of a segment-file image. Structural damage
    raises SegmentCorruptedError."""
    nm = len(MAGIC)
    head, tail = raw[:nm], raw[-nm:]
    if len(raw) < 2 * nm + 16 or head not in (MAGIC, MAGIC_V2) or tail != head:
        raise SegmentCorruptedError(f"{label}: not a PTSEG file", path=label)
    if tail == MAGIC_V2:  # legacy: structural checks + per-entry CRCs only
        index_off, index_len = np.frombuffer(raw[-nm - 16 : -nm], dtype="<u8")
        return None, (int(index_off), int(index_len))
    index_off, index_len = np.frombuffer(raw[-FOOTER_V3 : -nm - 4], dtype="<u8")
    return int(np.frombuffer(raw[-nm - 4 : -nm], dtype="<u4")[0]), (int(index_off), int(index_len))


def _index_map(raw: bytes, index: tuple[int, int], label: str) -> dict:
    try:
        meta = json.loads(raw[index[0] : index[0] + index[1]].decode("utf-8"))
        if "entries" not in meta:
            raise KeyError("entries")
    except (UnicodeDecodeError, json.JSONDecodeError, KeyError, TypeError) as e:
        raise SegmentCorruptedError(f"{label}: damaged index map ({e})", path=label) from e
    return meta


def dictionary_cardinality(meta: dict, column: str) -> int | None:
    """Entries of a column's dictionary by the index map, None for a raw column."""
    e = meta["entries"].get(f"dict::{column}")
    if e is None:
        return None
    return int(e["n"] if e["kind"] == "str" else e["shape"][0])


def segment_file_crc(path: Path | str) -> int | None:
    """Stored whole-file CRC from a segment file's v03 footer — a 28-byte
    tail read, no full-file IO — or None for legacy v02 files. This is the
    value the controller records as `fileCrc` in ZK segment metadata."""
    path = Path(path)
    if path.is_dir():
        path = path / SEGMENT_FILE
    nm = len(MAGIC)
    with open(path, "rb") as f:
        size = f.seek(0, 2)
        if size < FOOTER_V3:
            return None
        f.seek(size - FOOTER_V3)
        foot = f.read(FOOTER_V3)
    if foot[-nm:] != MAGIC:
        return None
    return int(np.frombuffer(foot[16:20], dtype="<u4")[0])


def verify_segment_bytes(raw: bytes, label: str = "<bytes>", expected_crc: int | None = None) -> int:
    """Integrity-check a segment-file image in memory: structural magic
    checks, whole-file CRC against the v03 footer, and (optionally) the
    `fileCrc` recorded in ZK segment metadata — which catches a footer
    damaged/forged in concert with the payload. Returns the verified CRC;
    raises SegmentCorruptedError on any mismatch. Legacy v02 images get
    structural verification only and return a CRC over the entire image as
    their fingerprint."""
    stored, _ = _footer(raw, label)
    if stored is None:
        return native.crc32(raw)
    if native.crc32(memoryview(raw)[:-FOOTER_V3]) != stored:
        raise SegmentCorruptedError(f"{label}: whole-file CRC mismatch", path=label)
    if expected_crc is not None and stored != expected_crc:
        raise SegmentCorruptedError(
            f"{label}: CRC {stored} != cluster metadata fileCrc {expected_crc}",
            path=label,
        )
    return stored


def verify_segment_file(path: Path | str, expected_crc: int | None = None) -> int:
    """Full-file integrity check of an on-disk segment file (or segment
    dir); see verify_segment_bytes for the verification contract."""
    path = Path(path)
    if path.is_dir():
        path = path / SEGMENT_FILE
    try:
        raw = _map_file(path)
    except OSError as e:
        raise SegmentCorruptedError(f"{path}: unreadable ({e})", path=str(path)) from e
    return verify_segment_bytes(raw, str(path), expected_crc)
