"""Property-based tests for the cascading encoding framework (§2.6) and the
per-encoding deletion-masking rules (§2.1)."""

import struct

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.encodings import (BY_NAME, EncodeContext, blob_encoding_name,
                                  decode_blob, decode_strings, encode_array,
                                  encode_strings, mask_blob)
from repro.core.encodings.base import frame, unframe
from repro.core.encodings.numeric import _split2

DTYPES = [np.int64, np.int32, np.uint32, np.uint64, np.int16, np.uint8]


@st.composite
def int_arrays(draw):
    dtype = draw(st.sampled_from(DTYPES))
    n = draw(st.integers(1, 400))
    info = np.iinfo(dtype)
    kind = draw(st.sampled_from(["random", "runs", "small", "constant",
                                 "sorted"]))
    rng = np.random.default_rng(draw(st.integers(0, 2**31)))
    if kind == "random":
        arr = rng.integers(info.min, info.max, n, dtype=np.int64 if info.min < 0 else np.uint64)
    elif kind == "runs":
        arr = np.repeat(rng.integers(0, 50, max(n // 7, 1)), 7)[:n]
    elif kind == "small":
        arr = rng.integers(0, 100, n)
    elif kind == "constant":
        arr = np.full(n, int(rng.integers(0, 1000)))
    else:
        arr = np.sort(rng.integers(0, 10**6, n))
    # clip bounds must be representable in arr's dtype (int64/uint64), not
    # just the target dtype — np.clip(int64_arr, 0, uint64_max) overflows
    ainfo = np.iinfo(arr.dtype)
    return np.clip(arr, max(info.min, ainfo.min),
                   min(info.max, ainfo.max)).astype(dtype)


@st.composite
def float_arrays(draw):
    dtype = draw(st.sampled_from([np.float32, np.float64]))
    n = draw(st.integers(1, 300))
    rng = np.random.default_rng(draw(st.integers(0, 2**31)))
    kind = draw(st.sampled_from(["random", "decimal", "smooth", "constant"]))
    if kind == "random":
        arr = rng.normal(size=n) * 10.0 ** float(rng.integers(-3, 6))
    elif kind == "decimal":
        arr = np.round(rng.random(n) * 1000, 2)
    elif kind == "smooth":
        arr = np.cumsum(rng.normal(0, 0.01, n))
    else:
        arr = np.full(n, float(rng.random()))
    return arr.astype(dtype)


@settings(max_examples=60, deadline=None)
@given(int_arrays())
def test_int_roundtrip(arr):
    blob = encode_array(arr)
    out = decode_blob(blob)
    assert out.dtype == arr.dtype
    assert np.array_equal(out, arr)


@settings(max_examples=40, deadline=None)
@given(float_arrays())
def test_float_roundtrip(arr):
    blob = encode_array(arr)
    out = decode_blob(blob)
    assert out.dtype == arr.dtype
    assert np.array_equal(out, arr, equal_nan=True)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31), st.integers(1, 500), st.floats(0.0, 1.0))
def test_bool_roundtrip(seed, n, p):
    rng = np.random.default_rng(seed)
    arr = rng.random(n) < p
    out = decode_blob(encode_array(arr))
    assert np.array_equal(out, arr)


@settings(max_examples=30, deadline=None)
@given(int_arrays(), st.data())
def test_mask_size_criterion_and_erasure(arr, data):
    """§2.1: masking never grows the page; survivors decode unchanged."""
    if len(arr) < 3:
        return
    blob = encode_array(arr)
    k = data.draw(st.integers(1, min(8, len(arr))))
    pos = np.asarray(sorted(data.draw(
        st.sets(st.integers(0, len(arr) - 1), min_size=k, max_size=k))))
    masked = mask_blob(blob, pos, len(arr))
    if masked is None:
        return  # DV-only fallback is allowed (relocation path covers it)
    assert len(masked) == len(blob)  # the paper's size criterion
    out = decode_blob(masked)
    keep = np.ones(len(arr), bool)
    keep[pos] = False
    if len(out) == len(arr):          # masked in place
        assert np.array_equal(out[keep], arr[keep])
    else:                             # compact-deleted (RLE)
        assert np.array_equal(out, arr[keep])


@pytest.mark.parametrize("enc_name", ["fixed_bit_width", "varint", "for",
                                      "dictionary", "trivial"])
def test_native_mask_in_place(enc_name):
    """The paper's five maskable encodings must mask without decode-reencode."""
    rng = np.random.default_rng(0)
    # low cardinality so dictionary is applicable; fine for the rest too
    arr = rng.integers(0, 16, 256).astype(np.int64)
    enc = BY_NAME[enc_name]
    blob = enc.encode(arr, EncodeContext(candidates=(enc_name,)))
    assert blob is not None
    masked = mask_blob(blob, np.array([0, 100, 255]), len(arr))
    assert masked is not None and len(masked) == len(blob)


def test_strings_roundtrip():
    strings = [b"http://example.com/%d" % i for i in range(200)] + [b"", b"\xff" * 5]
    assert decode_strings(encode_strings(strings)) == strings


def _string_blob(strings, data_frame):
    """A string column blob whose data bytes are framed by ``data_frame``."""
    from repro.core.encodings.bytes_ import STRING_MAGIC
    from repro.core.encodings.numeric import _cat
    lens = np.asarray([len(s) for s in strings], np.int64)
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    data = data_frame(b"".join(strings))
    assert data is not None
    return struct.pack("<BQ", STRING_MAGIC, len(strings)) \
        + _cat(encode_array(offsets), data)


def _stored(data):
    raw = BY_NAME["raw_bytes"]
    return frame(raw.eid, struct.pack("<QB", len(data), raw.STORED), data)


STRING_DATA = {
    "raw_bytes-zstd": lambda d: BY_NAME["raw_bytes"].encode(d, EncodeContext()),
    "raw_bytes-stored": _stored,
    "fsst_lite": lambda d: BY_NAME["fsst_lite"].encode(d, EncodeContext()),
}

STRING_ROWS = {
    "empty": lambda n, rng: np.zeros(0, np.int64),
    "one": lambda n, rng: np.asarray([7]),
    "last": lambda n, rng: np.asarray([n - 1]),
    "2pct": lambda n, rng: np.sort(rng.choice(n, n // 50, replace=False)),
    "all": lambda n, rng: np.arange(n),
}


@pytest.mark.parametrize("rows", sorted(STRING_ROWS))
@pytest.mark.parametrize("data", sorted(STRING_DATA))
def test_decode_strings_rows(data, rows):
    """Decoding at sorted rows gives the full decode's strings at those
    rows, in order, for every data encoding; empty strings included."""
    strings = [b"" if i % 11 == 0 else
               b"https://img.example.com/%d/photo-%s.jpg" % (i % 97,
                                                             b"x" * (i % 13))
               + (b"\xf3\xff" if i % 29 == 0 else b"")
               for i in range(800)]
    strings[-1] = b""
    blob = _string_blob(strings, STRING_DATA[data])
    data_blob = _split2(memoryview(blob)[9:])[1]
    name, _, codec = data.partition("-")
    assert blob_encoding_name(data_blob) == name
    if codec:
        raw = BY_NAME["raw_bytes"]
        header = unframe(data_blob)[1]
        assert header[8] == {"zstd": raw.ZSTD, "stored": raw.STORED}[codec]
    full = decode_strings(blob)
    assert full == strings
    sel = STRING_ROWS[rows](len(strings), np.random.default_rng(3))
    got = decode_strings(blob, sel)
    assert got == [full[i] for i in sel]
    assert all(type(s) is bytes for s in got)


def test_cascade_never_worse_than_trivial():
    rng = np.random.default_rng(1)
    for arr in [rng.integers(0, 2**60, 1000).astype(np.int64),
                rng.normal(size=1000).astype(np.float32)]:
        blob = encode_array(arr)
        assert len(blob) <= arr.nbytes + 128


def test_every_registered_encoding_has_unique_eid():
    from repro.core.encodings import REGISTRY
    assert len(REGISTRY) >= 14
    names = [e.name for e in REGISTRY.values()]
    assert len(set(names)) == len(names)


# ---------------------------------------------------------------------------
# bit unpacking: the word-at-a-time unpack against the bit-matrix algorithm
# ---------------------------------------------------------------------------

def _unpack_bits_reference(buf, n, width):
    """The bit-matrix unpack: each value's bits as a (n, width) uint8 matrix,
    widened to uint64, shifted into place and summed."""
    if width == 0 or n == 0:
        return np.zeros(n, np.uint64)
    raw = np.frombuffer(buf, np.uint8, count=(n * width + 7) // 8)
    bits = np.unpackbits(raw, count=n * width, bitorder="little").reshape(n, width)
    shifts = np.arange(width, dtype=np.uint64)
    return (bits.astype(np.uint64) << shifts).sum(axis=1, dtype=np.uint64)


def _random_bits(rng, n, width):
    """n uniformly random values of exactly ``width`` bits, top bit set."""
    if width == 0:
        return np.zeros(n, np.uint64)
    vals = rng.integers(0, 2**63, n, dtype=np.uint64) << np.uint64(1)
    vals |= rng.integers(0, 2, n, dtype=np.uint64)
    vals &= np.uint64((1 << width) - 1)
    if n:
        vals[0] = np.uint64((1 << width) - 1)
    return vals


@pytest.mark.parametrize("n", [0, 1, 7, 8, 9, 8191, 8192, 65536])
@pytest.mark.parametrize("width", range(65))
def test_unpack_bits_matches_bit_matrix(width, n):
    from repro.core.encodings.numeric import pack_bits, unpack_bits
    vals = _random_bits(np.random.default_rng([width, n]), n, width)
    packed = pack_bits(vals, width)
    assert len(packed) == (n * width + 7) // 8
    # an unaligned slice of a larger buffer: a junk byte in front, set bits
    # past the last value and trailing bytes after the stream
    junk = bytearray(b"\xa5" + packed + b"\xff" * 11)
    if n * width % 8:
        junk[len(packed)] |= (0xFF << (n * width % 8)) & 0xFF
    view = memoryview(junk)[1:len(packed) + 6]
    for buf in (packed, view):
        want = _unpack_bits_reference(buf, n, width)
        got = unpack_bits(buf, n, width)
        assert got.dtype == np.uint64 and got.shape == (n,)
        assert np.array_equal(got, want)
        assert np.array_equal(got, vals)



@pytest.mark.parametrize("width", [6, 13, 61])
def test_unpack_bits_offsets_one_entry_per_width(width):
    """Pages of many lengths share one offset pair per width, grown to the
    longest and sliced for shorter pages."""
    from repro.core.encodings.numeric import _OFFSETS, pack_bits, unpack_bits
    rng = np.random.default_rng(width)
    longest = len(_OFFSETS[width][0]) if width in _OFFSETS else 0
    for n in (70_000, 3, 8192, 70_001, 9, 1):
        vals = _random_bits(rng, n, width)
        assert np.array_equal(unpack_bits(pack_bits(vals, width), n, width), vals)
        longest = max(longest, n)
        byte, shift = _OFFSETS[width]
        assert len(byte) == len(shift) == longest
        assert not byte.flags.writeable and not shift.flags.writeable

# Pages at the edges of the bit-packed encodings: negative and int64-minimum
# bases, width 64, BF16 codes (uint16) and narrow signed dtypes.
_I64 = np.iinfo(np.int64)
_EDGE_PAGES = {
    "for": {
        "negative_lo": np.arange(-700, 300, 3, dtype=np.int32),
        "int64_min_lo": np.array([_I64.min, 0, -1, _I64.max, 5] * 13, np.int64),
        "int64_min_only": np.array([_I64.min] * 9 + [_I64.min + 1], np.int64),
        "uint64_width_64": np.array([0, 2**64 - 1, 2**63, 1] * 5, np.uint64),
        "bf16_codes": np.random.default_rng(3).integers(
            0x3F00, 0x4100, 8192).astype(np.uint16),
        "int8_full": np.arange(-128, 128, dtype=np.int8),
        "int16_negative": np.array([-32768, -1, -200, -32000] * 7, np.int16),
    },
    "fixed_bit_width": {
        "uint64_width_64": np.array([0, 2**64 - 1, 2**63, 7] * 5, np.uint64),
        "int64_width_63": np.array([_I64.max, 0, 1, 2**62] * 5, np.int64),
        "bf16_codes": np.random.default_rng(4).integers(
            0, 0xFFFF, 8191).astype(np.uint16),
        "int32_small": np.arange(0, 1000, 7, dtype=np.int32),
        "uint8": np.arange(256, dtype=np.uint8),
    },
    "dictionary": {
        "negative": np.array([-5, -1, 3, -5, 3, 3, -1] * 11, np.int32),
        "int64_extremes": np.array([_I64.min, _I64.max, 0] * 21, np.int64),
        "bf16_codes": np.random.default_rng(5).choice(
            np.array([0x3F80, 0x4000, 0x3E00, 0xBF80], np.uint16), 8192),
        "float32": np.array([0.5, -2.25, 0.5, 1e30] * 9, np.float32),
        "uint64_top": np.array([2**64 - 1, 0, 2**63] * 8, np.uint64),
    },
}


def _decode_reference(enc_name, header, payload):
    """Each decoder as it was over the bit-matrix unpack."""
    import struct

    from repro.core.encodings.base import code_dtype
    from repro.core.encodings.numeric import _split2
    if enc_name == "for":
        code, n, lo, width = struct.unpack_from("<BQqB", header)
        u = _unpack_bits_reference(payload, n, width)
        return (u.astype(np.int64) + lo).astype(code_dtype(code))
    if enc_name == "fixed_bit_width":
        code, n, width = struct.unpack_from("<BQB", header)
        return _unpack_bits_reference(payload, n, width).astype(code_dtype(code))
    code, n, nuniq, width = struct.unpack_from("<BQQB", header)
    vblob, packed = _split2(payload)
    values = decode_blob(vblob)
    codes = _unpack_bits_reference(packed, n, width).astype(np.int64)
    masked = codes >= nuniq
    out = values[np.where(masked, 0, codes)]
    out[masked] = 0
    return out.astype(code_dtype(code))


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
@pytest.mark.parametrize("enc_name,case", [
    (e, c) for e, cases in _EDGE_PAGES.items() for c in cases])
def test_bit_packed_pages_decode_unchanged(enc_name, case, masked):
    from repro.core.encodings.base import unframe
    arr = _EDGE_PAGES[enc_name][case]
    enc = BY_NAME[enc_name]
    blob = enc.encode(arr, EncodeContext(candidates=(enc_name,)))
    assert blob is not None
    keep = np.ones(len(arr), bool)
    if masked:
        pos = np.array([0, len(arr) // 2, len(arr) - 1])
        keep[pos] = False
        blob = mask_blob(blob, pos, len(arr))
        assert blob is not None and blob_encoding_name(blob) == enc_name
    _, header, payload, _ = unframe(blob)
    out = decode_blob(blob)
    want = _decode_reference(enc_name, header, payload)
    assert out.dtype == arr.dtype == want.dtype
    assert np.array_equal(out, want)
    assert np.array_equal(out[keep], arr[keep])
    if masked:
        # masked elements decode to the encoding's neutral value
        neutral = {"for": arr.min(), "fixed_bit_width": 0, "dictionary": 0}
        assert np.all(out[~keep] == neutral[enc_name])
