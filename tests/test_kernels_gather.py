"""`kernels._gather_rows`: the rows' gather through a resident integer operand
(`lookUp`'s join, an expression key's buckets) reads a row of the table as
(entries / 128, 128) and picks the lane, a block of codes at a time. It has to
equal `table[codes]` bit for bit, whatever the table's size, the dtype the
codes are staged in and the number of rows against the block, and no program
that holds it may hold the (rows, 128) array a whole segment's rows would
gather.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pinot_tpu.query import kernels

BLOCK, LANES = kernels._GATHER_BLOCK, kernels._GATHER_LANES

#: rows: fewer than a block, whole blocks, and blocks with a tail
ROWS = {"under-a-block": 1000, "whole-blocks": 2 * BLOCK, "blocks-and-a-tail": 2 * BLOCK + 1024 + 7}
CODES = {"uint8": np.uint8, "int16": np.int16, "int32": np.int32}
CASES = [
    pytest.param(entries, dtype, rows, id=f"{entries}-{dtype}-{rows}")
    for entries in (1, 2, 64, 128, 2048, 4096, 1_048_576)
    for dtype in CODES
    if entries - 1 <= np.iinfo(CODES[dtype]).max  # a dtype that holds the table's last code
    for rows in ROWS
]


def table_and_codes(entries: int, dtype: str, rows: int):
    """An operand as a plan leaves it (a dictionary's words, then a miss word
    up to a power of two) and codes that reach its first entry, its last,
    the padding's miss word and, with the last row, the end of a block's tail."""
    rng = np.random.default_rng(entries * 31 + rows)
    table = rng.integers(-(2**31), 2**31 - 1, size=entries, dtype=np.int64).astype(np.int32)
    known = max(entries * 3 // 4, 1)
    table[known:] = np.int32(0x7FFF_0000 | 77)  # the padding past the dictionary: every field reads a miss
    codes = rng.integers(0, entries, size=rows).astype(CODES[dtype])
    codes[:3] = (0, entries - 1, min(known, entries - 1))
    codes[-1] = entries - 1
    return table, codes


@pytest.mark.parametrize("jitted", [True, False], ids=["jit", "eager"])
@pytest.mark.parametrize("entries, dtype, rows", CASES)
def test_the_gather_equals_indexing_bit_for_bit(entries, dtype, rows, jitted):
    table, codes = table_and_codes(entries, dtype, ROWS[rows])
    want = table[codes.astype(np.int64)]
    for in_bounds in (False, True):  # an expression key's call, lookUp's call

        def gather(t, c, in_bounds=in_bounds):
            return kernels._gather_rows(t, c, in_bounds=in_bounds)

        got = (jax.jit(gather) if jitted else gather)(jnp.asarray(table), jnp.asarray(codes))
        assert got.dtype == jnp.int32 and got.shape == want.shape
        assert np.array_equal(np.asarray(got), want), in_bounds


def test_a_code_outside_the_table_reads_what_indexing_reads():
    """Without the promise, an expression key's call: a negative code counts
    from the end and what is still outside is clamped, in a table of whole
    rows and in one padded to a row."""
    for entries in (5, 256):
        table = jnp.arange(1, entries + 1, dtype=jnp.int32)
        codes = jnp.asarray([0, entries - 1, entries, entries + 200, 1 << 20, -1, -entries, -entries - 1, -(1 << 20)], dtype=jnp.int32)
        assert np.array_equal(np.asarray(kernels._gather_rows(table, codes)), np.asarray(table[codes]))


@pytest.mark.parametrize("call", ["_lookup_gather", "_key_gather"])
def test_a_segment_of_rows_never_gathers_rows_by_lanes(call):
    """A 1M-row gather through either call site: the compiled program's
    temporaries stay under the (rows, 128) int32 array (512 MiB here, 2 GiB
    for a segment of 4M rows) and no buffer of the HLO has that shape; the
    largest is a block's."""
    rows = 1 << 20
    assert rows > BLOCK, "the case has to span several blocks"
    lowered = jax.jit(getattr(kernels, call)).lower(
        jax.ShapeDtypeStruct((4096,), jnp.int32), jax.ShapeDtypeStruct((rows,), jnp.int32)
    )
    compiled = lowered.compile()
    shapes = {tuple(int(d) for d in m.split(",")) for m in re.findall(r"s32\[(\d+(?:,\d+)+)\]", compiled.as_text())}
    assert (BLOCK, LANES) in shapes, "the block's rows were expected among the program's buffers"
    assert not [s for s in shapes if int(np.prod(s)) >= rows * LANES], shapes
    stats = compiled.memory_analysis()
    if stats is None or not hasattr(stats, "temp_size_in_bytes"):
        pytest.skip("this backend reports no memory analysis of a compiled program")
    assert stats.temp_size_in_bytes < rows * LANES  # a quarter of that array's bytes


@pytest.mark.parametrize("entries", [1, 2, 8, 32, 64, 256, 1000, 4096, 8192])
@pytest.mark.parametrize("share", [0.0, 0.1, 1.0])
def test_a_membership_table_read_as_bits_is_the_gather(entries, share):
    """`kernels._in_lut`: up to 4,096 entries a table is packed 32 entries a
    word and a row picks its word and its bit; past that it is `lut[ids]` itself."""
    rng = np.random.default_rng(entries)
    lut = rng.random(entries) < share
    ids = rng.integers(0, entries, 5000).astype(np.int8 if entries <= 64 else np.int16 if entries <= 4096 else np.int32)
    got = np.asarray(jax.jit(kernels._in_lut)(jnp.asarray(lut), jnp.asarray(ids)))
    assert got.dtype == bool and np.array_equal(got, lut[ids])
    text = jax.jit(kernels._in_lut).lower(jnp.asarray(lut), jnp.asarray(ids)).as_text()
    assert ("gather" in text) == (entries > 32 * kernels._LUT_WORDS_MAX)
