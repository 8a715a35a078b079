"""Native C++ runtime kernels (ctypes-bound), with pure-numpy fallbacks.

Reference parity: SURVEY.md §2 native-component ledger — the reference keeps
its hot host paths in off-heap/Unsafe Java + external native libs
(RoaringBitmap, lz4/zstd); here they are C++ (csrc/pinot_native.cpp) compiled
once on demand with g++ and loaded via ctypes. Every function has a numpy
fallback so the framework runs (slower) when no toolchain is present
(PINOT_TPU_NO_NATIVE=1 forces fallbacks, used in tests for differential
checking).

Public API (see each function's docstring): bitpack/bitunpack, lz4_compress/
lz4_decompress, bitmap algebra (bm_*), hash64/hash_bytes, hll_update/merge/
estimate, masked_stats, group_* loops, hash_group_ids, crc32.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import zlib
from pathlib import Path

import numpy as np

_HERE = Path(__file__).parent
_SRC = _HERE / "csrc" / "pinot_native.cpp"
_BUILD = _HERE / "_build"

_lib = None
#: why the numpy fallbacks are in use, when they are (None once the library loaded)
_fallback_reason: str | None = None


def _lib_path() -> Path:
    """The built library is keyed by a hash of its source: `_build/` is not
    committed, so a stale .so left by another checkout of the tree can never
    be the one that loads."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _BUILD / f"libpinot_native.{digest}.so"


def _try_build_and_load():
    global _lib, _fallback_reason
    if os.environ.get("PINOT_TPU_NO_NATIVE"):
        _fallback_reason = "PINOT_TPU_NO_NATIVE set"
        return
    try:
        lib_path = _lib_path()
        if not lib_path.exists():
            _BUILD.mkdir(exist_ok=True)
            # per-process tmp name: concurrent first imports must not tear the .so
            tmp = _BUILD / f"{lib_path.name}.tmp.{os.getpid()}"
            subprocess.run(
                ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", str(_SRC), "-o", str(tmp), "-ldl"],
                check=True,
                capture_output=True,
                timeout=300,
            )
            os.replace(tmp, lib_path)
        lib = ctypes.CDLL(str(lib_path))
        abi = lib.pt_abi_version()
        if abi != 1:
            _fallback_reason = f"abi version {abi} != 1"
            return
        _declare(lib)
        _lib = lib
    except subprocess.CalledProcessError as e:
        _fallback_reason = f"g++ exit {e.returncode}: {e.stderr.decode(errors='replace').strip()[-200:]}"
    except (OSError, subprocess.TimeoutExpired, AttributeError) as e:
        # no toolchain, unloadable library, build timeout, missing symbol
        _fallback_reason = f"{type(e).__name__}: {e}"


def _declare(lib):
    i64, i32, f64 = ctypes.c_int64, ctypes.c_int32, ctypes.c_double
    p = ctypes.c_void_p
    lib.pt_bitpack_words.restype = i64
    lib.pt_bitpack_words.argtypes = [i64, i32]
    lib.pt_bitpack32.restype = None
    lib.pt_bitpack32.argtypes = [p, i64, i32, p]
    lib.pt_bitunpack32.restype = None
    lib.pt_bitunpack32.argtypes = [p, i64, i32, p]
    lib.pt_lz4_compress_bound.restype = i64
    lib.pt_lz4_compress_bound.argtypes = [i64]
    lib.pt_lz4_compress.restype = i64
    lib.pt_lz4_compress.argtypes = [p, i64, p, i64]
    lib.pt_lz4_decompress.restype = i64
    lib.pt_lz4_decompress.argtypes = [p, i64, p, i64]
    # system chunk codecs (dlopen'd zstd/zlib/snappy; -2 = lib unavailable)
    for name, has_level in (
        ("pt_zstd", True),
        ("pt_gzip", True),
        ("pt_snappy", False),
    ):
        getattr(lib, f"{name}_bound").restype = i64
        getattr(lib, f"{name}_bound").argtypes = [i64]
        comp = getattr(lib, f"{name}_compress")
        comp.restype = i64
        comp.argtypes = [p, i64, p, i64] + ([i32] if has_level else [])
        dec = getattr(lib, f"{name}_decompress")
        dec.restype = i64
        dec.argtypes = [p, i64, p, i64]
    for nm in ("pt_bm_and", "pt_bm_or", "pt_bm_andnot"):
        fn = getattr(lib, nm)
        fn.restype = None
        fn.argtypes = [p, p, p, i64]
    lib.pt_bm_not.restype = None
    lib.pt_bm_not.argtypes = [p, p, i64]
    lib.pt_bm_cardinality.restype = i64
    lib.pt_bm_cardinality.argtypes = [p, i64]
    lib.pt_bm_extract.restype = i64
    lib.pt_bm_extract.argtypes = [p, i64, p, i64]
    lib.pt_bm_from_indices.restype = None
    lib.pt_bm_from_indices.argtypes = [p, i64, p, i64]
    lib.pt_hash64.restype = None
    lib.pt_hash64.argtypes = [p, i64, p]
    lib.pt_hash_bytes.restype = None
    lib.pt_hash_bytes.argtypes = [p, p, i64, p]
    lib.pt_hll_update.restype = None
    lib.pt_hll_update.argtypes = [p, p, i64, i32, p]
    lib.pt_hll_merge.restype = None
    lib.pt_hll_merge.argtypes = [p, p, i64]
    lib.pt_hll_estimate.restype = f64
    lib.pt_hll_estimate.argtypes = [p, i32]
    lib.pt_masked_stats_f64.restype = None
    lib.pt_masked_stats_f64.argtypes = [p, p, i64, p]
    for nm in ("pt_group_sum_f64", "pt_group_min_f64", "pt_group_max_f64"):
        fn = getattr(lib, nm)
        fn.restype = None
        fn.argtypes = [p, p, p, i64, p]
    lib.pt_group_count.restype = None
    lib.pt_group_count.argtypes = [p, p, i64, p]
    lib.pt_hash_group_ids.restype = i64
    lib.pt_hash_group_ids.argtypes = [p, i64, p, p, i64, p]


_try_build_and_load()


def available() -> bool:
    """True when the C++ library compiled and loaded."""
    return _lib is not None


def status() -> str:
    """`built`, or `fallback:<reason>` when the numpy paths are in use."""
    return "built" if _lib is not None else f"fallback:{_fallback_reason}"


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


def _mask_arg(mask):
    if mask is None:
        return ctypes.c_void_p(0), None
    m = np.ascontiguousarray(mask, dtype=np.uint8)
    return _ptr(m), m


# -- fixed-bit packing -------------------------------------------------------


def bits_needed(cardinality: int) -> int:
    """Bits per value for dict ids in [0, cardinality)."""
    return max(1, int(cardinality - 1).bit_length()) if cardinality > 1 else 1


def bitpack(ids: np.ndarray, bits: int) -> np.ndarray:
    """Pack uint32/int32 values of `bits` significant bits into uint64 words."""
    ids = np.ascontiguousarray(ids, dtype=np.uint32)
    n = len(ids)
    nwords = (n * bits + 63) // 64
    out = np.zeros(nwords, dtype=np.uint64)
    if _lib is not None:
        _lib.pt_bitpack32(_ptr(ids), n, bits, _ptr(out))
        return out
    # fallback: expand to an (n, bits) bit matrix and scatter-or into words
    positions = np.arange(n, dtype=np.int64) * bits
    pos = positions[:, None] + np.arange(bits)[None, :]  # (n, bits)
    word = (pos >> 6).ravel()
    shift = (pos & 63).ravel().astype(np.uint64)
    bitmat = ((ids[:, None] >> np.arange(bits, dtype=np.uint32)[None, :]) & np.uint32(1)).astype(np.uint64)
    np.bitwise_or.at(out, word, bitmat.ravel() << shift)
    return out


def bitunpack(words: np.ndarray, n: int, bits: int) -> np.ndarray:
    """Inverse of bitpack: recover n uint32 values."""
    words = np.ascontiguousarray(words, dtype=np.uint64)
    out = np.empty(n, dtype=np.uint32)
    if _lib is not None:
        _lib.pt_bitunpack32(_ptr(words), n, bits, _ptr(out))
        return out
    positions = np.arange(n, dtype=np.int64) * bits
    pos = positions[:, None] + np.arange(bits)[None, :]
    bitvals = (words[pos >> 6] >> (pos & 63).astype(np.uint64)) & np.uint64(1)
    out[:] = (bitvals.astype(np.uint32) << np.arange(bits, dtype=np.uint32)[None, :]).sum(
        axis=1, dtype=np.uint32
    )
    return out


# -- LZ4 block codec ---------------------------------------------------------


def lz4_compress(data: bytes | np.ndarray) -> bytes:
    """LZ4-block-compress bytes; raises RuntimeError without the native lib
    (callers choose codec 'raw' when unavailable)."""
    buf = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray, memoryview)) else np.ascontiguousarray(data, dtype=np.uint8)
    if _lib is None:
        raise RuntimeError("native lz4 unavailable")
    cap = _lib.pt_lz4_compress_bound(len(buf))
    out = np.empty(cap, dtype=np.uint8)
    k = _lib.pt_lz4_compress(_ptr(buf), len(buf), _ptr(out), cap)
    if k < 0:
        raise RuntimeError("lz4 compress failed")
    return out[:k].tobytes()


def lz4_decompress(data: bytes, raw_len: int) -> bytes:
    buf = np.frombuffer(data, dtype=np.uint8)
    if _lib is None:
        out_b = _lz4_decompress_py(bytes(data), raw_len)
        if len(out_b) != raw_len:
            raise RuntimeError(f"lz4 decompress: got {len(out_b)}, want {raw_len}")
        return out_b
    out = np.empty(raw_len, dtype=np.uint8)
    k = _lib.pt_lz4_decompress(_ptr(buf), len(buf), _ptr(out), raw_len)
    if k != raw_len:
        raise RuntimeError(f"lz4 decompress: got {k}, want {raw_len}")
    return out.tobytes()


# -- system chunk codecs (ZSTD / GZIP / Snappy) ------------------------------
# ChunkCompressionType parity (pinot-segment-spi/.../compression/
# ChunkCompressionType.java:22): ZSTANDARD, GZIP, SNAPPY via dlopen'd system
# libraries. Like the reference, a reading host must have the codec a segment
# was written with — except lz4 (pure-python decoder below) and gzip (stdlib
# zlib fallback); zstd/snappy segments require the system library to load.

_CODEC_LEVELS = {"zstd": 3, "gzip": 6}


def codec_available(codec: str) -> bool:
    """True when `codec` can round-trip on this host."""
    if codec in ("raw",):
        return True
    if _lib is None:
        return False
    if codec == "lz4":
        return True
    if codec not in ("zstd", "gzip", "snappy"):
        return False
    return int(getattr(_lib, f"pt_{codec}_bound")(1)) > 0


def chunk_compress(data: bytes, codec: str) -> bytes:
    """Compress with the named codec ('lz4'/'zstd'/'gzip'/'snappy')."""
    if codec == "lz4":
        return lz4_compress(data)
    if _lib is None:
        raise RuntimeError(f"native {codec} unavailable")
    buf = np.frombuffer(data, dtype=np.uint8)
    cap = int(getattr(_lib, f"pt_{codec}_bound")(len(buf)))
    if cap < 0:
        raise RuntimeError(f"{codec} library unavailable")
    out = np.empty(max(cap, 16), dtype=np.uint8)
    args = [_ptr(buf), len(buf), _ptr(out), len(out)]
    if codec in _CODEC_LEVELS:
        args.append(_CODEC_LEVELS[codec])
    k = int(getattr(_lib, f"pt_{codec}_compress")(*args))
    if k < 0:
        raise RuntimeError(f"{codec} compress failed ({k})")
    return out[:k].tobytes()


def chunk_decompress(data: bytes, raw_len: int, codec: str) -> bytes:
    """Decompress `codec`-encoded bytes to exactly raw_len."""
    if codec == "raw":
        return bytes(data)
    if codec == "lz4":
        return lz4_decompress(data, raw_len)
    if _lib is None or int(getattr(_lib, f"pt_{codec}_bound")(1)) < 0:
        if codec == "gzip":
            # toolchain-less / libz-less hosts: stdlib zlib reads the same
            # zlib-format stream pt_gzip_compress writes
            import zlib

            out_b = zlib.decompress(bytes(data))
            if len(out_b) != raw_len:
                raise RuntimeError(f"gzip decompress: got {len(out_b)}, want {raw_len}")
            return out_b
        raise RuntimeError(f"native {codec} unavailable")
    buf = np.frombuffer(data, dtype=np.uint8)
    out = np.empty(max(raw_len, 1), dtype=np.uint8)
    k = int(getattr(_lib, f"pt_{codec}_decompress")(_ptr(buf), len(buf), _ptr(out), raw_len))
    if k != raw_len:
        raise RuntimeError(f"{codec} decompress: got {k}, want {raw_len}")
    return out[:raw_len].tobytes()


def _lz4_decompress_py(src: bytes, cap: int) -> bytes:
    """Pure-python LZ4 block decoder: segments written with the native codec
    must remain readable on toolchain-less hosts."""
    out = bytearray()
    i, n = 0, len(src)
    while i < n:
        token = src[i]
        i += 1
        llen = token >> 4
        if llen == 15:
            while True:
                if i >= n:
                    raise RuntimeError("lz4: truncated literal length")
                b = src[i]
                i += 1
                llen += b
                if b != 255:
                    break
        if i + llen > n or len(out) + llen > cap:
            raise RuntimeError("lz4: literal overrun")
        out += src[i : i + llen]
        i += llen
        if i >= n:
            break
        if i + 2 > n:
            raise RuntimeError("lz4: truncated offset")
        offset = src[i] | (src[i + 1] << 8)
        i += 2
        if offset == 0 or offset > len(out):
            raise RuntimeError("lz4: bad offset")
        mlen = (token & 15) + 4
        if (token & 15) == 15:
            while True:
                if i >= n:
                    raise RuntimeError("lz4: truncated match length")
                b = src[i]
                i += 1
                mlen += b
                if b != 255:
                    break
        if len(out) + mlen > cap:
            raise RuntimeError("lz4: match overrun")
        start = len(out) - offset
        for j in range(mlen):  # byte-wise: overlapping matches replicate
            out.append(out[start + j])
    return bytes(out)


# -- dense bitmaps -----------------------------------------------------------


def bm_words(n_docs: int) -> int:
    return (n_docs + 63) // 64


def bm_and(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    if _lib is not None:
        out = np.empty_like(a)
        _lib.pt_bm_and(_ptr(a), _ptr(b), _ptr(out), len(a))
        return out
    return a & b


def bm_or(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    if _lib is not None:
        out = np.empty_like(a)
        _lib.pt_bm_or(_ptr(a), _ptr(b), _ptr(out), len(a))
        return out
    return a | b


def bm_andnot(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    if _lib is not None:
        out = np.empty_like(a)
        _lib.pt_bm_andnot(_ptr(a), _ptr(b), _ptr(out), len(a))
        return out
    return a & ~b


def bm_not(a: np.ndarray) -> np.ndarray:
    if _lib is not None:
        out = np.empty_like(a)
        _lib.pt_bm_not(_ptr(a), _ptr(out), len(a))
        return out
    return ~a


def bm_cardinality(a: np.ndarray) -> int:
    if _lib is not None:
        return int(_lib.pt_bm_cardinality(_ptr(a), len(a)))
    return int(np.unpackbits(a.view(np.uint8)).sum())


def bm_extract(a: np.ndarray, cap: int | None = None) -> np.ndarray:
    """Bitmap -> sorted int32 doc ids."""
    if cap is None:
        cap = bm_cardinality(a)
    out = np.empty(cap, dtype=np.int32)
    if _lib is not None:
        k = _lib.pt_bm_extract(_ptr(a), len(a), _ptr(out), cap)
        return out[:k]
    bits = np.unpackbits(a.view(np.uint8), bitorder="little")
    idx = np.nonzero(bits)[0].astype(np.int32)
    return idx[:cap]


def bm_from_indices(idx: np.ndarray, n_docs: int) -> np.ndarray:
    nwords = bm_words(n_docs)
    idx = np.ascontiguousarray(idx, dtype=np.int32)
    if _lib is not None:
        out = np.empty(nwords, dtype=np.uint64)
        _lib.pt_bm_from_indices(_ptr(idx), len(idx), _ptr(out), nwords)
        return out
    bits = np.zeros(nwords * 64, dtype=np.uint8)
    bits[idx] = 1
    return np.packbits(bits, bitorder="little").view(np.uint64)


def bm_from_bool(mask: np.ndarray) -> np.ndarray:
    """Bool mask -> uint64-word bitmap (padded with zeros)."""
    nwords = bm_words(len(mask))
    bits = np.zeros(nwords * 64, dtype=np.uint8)
    bits[: len(mask)] = mask.astype(np.uint8)
    return np.packbits(bits, bitorder="little").view(np.uint64)


def bm_to_bool(a: np.ndarray, n_docs: int) -> np.ndarray:
    return np.unpackbits(a.view(np.uint8), bitorder="little")[:n_docs].astype(bool)


# -- hashing -----------------------------------------------------------------


def hash64(vals: np.ndarray) -> np.ndarray:
    """splitmix64 over int64/uint64 values."""
    v = np.ascontiguousarray(vals).view(np.uint64) if vals.dtype != np.uint64 else np.ascontiguousarray(vals)
    out = np.empty(len(v), dtype=np.uint64)
    if _lib is not None:
        _lib.pt_hash64(_ptr(v), len(v), _ptr(out))
        return out
    x = v + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def hash_bytes(blob: bytes, offsets: np.ndarray) -> np.ndarray:
    """FNV-1a + splitmix finalizer over var-length slices blob[off[i]:off[i+1]]."""
    n = len(offsets) - 1
    out = np.empty(n, dtype=np.uint64)
    data = np.frombuffer(blob, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    if _lib is not None:
        _lib.pt_hash_bytes(_ptr(data), _ptr(offsets), n, _ptr(out))
        return out
    FNV_OFF, FNV_P = np.uint64(1469598103934665603), np.uint64(1099511628211)
    for i in range(n):
        h = FNV_OFF
        for byte in data[offsets[i] : offsets[i + 1]]:
            h = np.uint64((int(h) ^ int(byte)) * int(FNV_P) & 0xFFFFFFFFFFFFFFFF)
        out[i] = h
    return hash64(out)


# -- HLL ---------------------------------------------------------------------


def hll_update(hashes: np.ndarray, mask: np.ndarray | None, p: int, regs: np.ndarray) -> None:
    hashes = np.ascontiguousarray(hashes, dtype=np.uint64)
    if _lib is not None:
        mptr, mkeep = _mask_arg(mask)
        _lib.pt_hll_update(_ptr(hashes), mptr, len(hashes), p, _ptr(regs))
        return
    h = hashes if mask is None else hashes[np.asarray(mask, bool)]
    idx = (h & np.uint64((1 << p) - 1)).astype(np.int64)
    rest = h >> np.uint64(p)
    # count trailing zeros of rest (+1); rest==0 -> 64-p+1
    rho = np.full(len(h), 64 - p + 1, dtype=np.uint8)
    nz = rest != 0
    lowbit = rest[nz] & (~rest[nz] + np.uint64(1))
    rho[nz] = (np.log2(lowbit.astype(np.float64)) + 1).astype(np.uint8)
    np.maximum.at(regs, idx, rho)


def hll_merge(src: np.ndarray, acc: np.ndarray) -> None:
    if _lib is not None:
        _lib.pt_hll_merge(_ptr(src), _ptr(acc), len(src))
        return
    np.maximum(acc, src, out=acc)


def hll_estimate(regs: np.ndarray, p: int) -> float:
    if _lib is not None:
        return float(_lib.pt_hll_estimate(_ptr(regs), p))
    m = 1 << p
    s = np.ldexp(1.0, -regs.astype(np.int32)).sum()
    alpha = {16: 0.673, 32: 0.697, 64: 0.709}.get(m, 0.7213 / (1.0 + 1.079 / m))
    e = alpha * m * m / s
    zeros = int((regs == 0).sum())
    if e <= 2.5 * m and zeros:
        e = m * np.log(m / zeros)
    return float(e)


# -- aggregation loops -------------------------------------------------------


def masked_stats(v: np.ndarray, mask: np.ndarray | None) -> tuple[float, float, float, int]:
    """(sum, min, max, count) over masked values."""
    v = np.ascontiguousarray(v, dtype=np.float64)
    if _lib is not None:
        out = np.empty(4, dtype=np.float64)
        mptr, mkeep = _mask_arg(mask)
        _lib.pt_masked_stats_f64(_ptr(v), mptr, len(v), _ptr(out))
        return float(out[0]), float(out[1]), float(out[2]), int(out[3])
    sel = v if mask is None else v[np.asarray(mask, bool)]
    if len(sel) == 0:
        return 0.0, float("inf"), float("-inf"), 0
    return float(sel.sum()), float(sel.min()), float(sel.max()), int(len(sel))


def group_sum(v: np.ndarray, gid: np.ndarray, mask: np.ndarray | None, n_groups: int) -> np.ndarray:
    v = np.ascontiguousarray(v, dtype=np.float64)
    gid = np.ascontiguousarray(gid, dtype=np.int32)
    acc = np.zeros(n_groups, dtype=np.float64)
    if _lib is not None:
        mptr, mkeep = _mask_arg(mask)
        _lib.pt_group_sum_f64(_ptr(v), _ptr(gid), mptr, len(v), _ptr(acc))
        return acc
    sel = slice(None) if mask is None else np.asarray(mask, bool)
    np.add.at(acc, gid[sel], v[sel])
    return acc


def group_count(gid: np.ndarray, mask: np.ndarray | None, n_groups: int) -> np.ndarray:
    gid = np.ascontiguousarray(gid, dtype=np.int32)
    acc = np.zeros(n_groups, dtype=np.int64)
    if _lib is not None:
        mptr, mkeep = _mask_arg(mask)
        _lib.pt_group_count(_ptr(gid), mptr, len(gid), _ptr(acc))
        return acc
    sel = slice(None) if mask is None else np.asarray(mask, bool)
    np.add.at(acc, gid[sel], 1)
    return acc


def group_min(v: np.ndarray, gid: np.ndarray, mask: np.ndarray | None, n_groups: int) -> np.ndarray:
    v = np.ascontiguousarray(v, dtype=np.float64)
    gid = np.ascontiguousarray(gid, dtype=np.int32)
    acc = np.full(n_groups, np.inf, dtype=np.float64)
    if _lib is not None:
        mptr, mkeep = _mask_arg(mask)
        _lib.pt_group_min_f64(_ptr(v), _ptr(gid), mptr, len(v), _ptr(acc))
        return acc
    sel = slice(None) if mask is None else np.asarray(mask, bool)
    np.minimum.at(acc, gid[sel], v[sel])
    return acc


def group_max(v: np.ndarray, gid: np.ndarray, mask: np.ndarray | None, n_groups: int) -> np.ndarray:
    v = np.ascontiguousarray(v, dtype=np.float64)
    gid = np.ascontiguousarray(gid, dtype=np.int32)
    acc = np.full(n_groups, -np.inf, dtype=np.float64)
    if _lib is not None:
        mptr, mkeep = _mask_arg(mask)
        _lib.pt_group_max_f64(_ptr(v), _ptr(gid), mptr, len(v), _ptr(acc))
        return acc
    sel = slice(None) if mask is None else np.asarray(mask, bool)
    np.maximum.at(acc, gid[sel], v[sel])
    return acc


def hash_group_ids(keys: np.ndarray) -> tuple[np.ndarray, int]:
    """Assign dense group ids (first-seen order) to uint64 hashed keys.

    High-cardinality group-by fallback (NoDictionary*GroupKeyGenerator analog).
    Returns (gid int32 array, n_groups).
    """
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    n = len(keys)
    if _lib is not None:
        cap = 1
        while cap < 2 * max(n, 1):
            cap <<= 1
        slot_keys = np.empty(cap, dtype=np.uint64)
        slot_gids = np.empty(cap, dtype=np.int32)
        gid = np.empty(n, dtype=np.int32)
        ng = _lib.pt_hash_group_ids(_ptr(keys), n, _ptr(slot_keys), _ptr(slot_gids), cap, _ptr(gid))
        return gid, int(ng)
    uniq, gid = np.unique(keys, return_inverse=True)
    # np.unique orders by value, not first-seen; remap to first-seen order
    first = np.full(len(uniq), n, dtype=np.int64)
    np.minimum.at(first, gid, np.arange(n))
    order = np.argsort(first, kind="stable")
    remap = np.empty(len(uniq), dtype=np.int32)
    remap[order] = np.arange(len(uniq), dtype=np.int32)
    return remap[gid].astype(np.int32), len(uniq)


# -- crc ---------------------------------------------------------------------


def crc32(data: bytes | np.ndarray, seed: int = 0) -> int:
    """CRC-32 (IEEE) of a buffer. zlib's: it runs 180 MB in 0.09 s where this
    library's own byte-at-a-time table loop (`pt_crc32`, gone since) took
    0.57 s for the same number; it releases the GIL and reads the buffer in
    place."""
    if isinstance(data, np.ndarray):
        data = np.ascontiguousarray(data).view(np.uint8)
    return zlib.crc32(data, seed)
