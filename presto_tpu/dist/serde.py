"""Page wire format for the DCN (inter-process) boundary.

Reference: presto-main execution/buffer/PagesSerde.java +
SerializedPage (block-encoded pages, per-block encodings +
aircompressor, length-prefixed) fetched by
operator/HttpPageBufferClient.java. The TPU translation keeps raw
arrays on ICI (collectives inside compiled programs, dist/executor.py)
and serializes ONLY at the process boundary, exactly as SURVEY §6.8
prescribes: "the HTTP shapes survive only at the pod boundary".

Wire format v3 (little-endian, per-array codec bytes — ISSUE 16):

    offset 0   b"PTP"      magic
    offset 3   b"3"        version byte (old b"PTP2" blobs carry 0x32
                           here and fail LOUDLY, never misparse)
    offset 4   flags       bit0: header JSON is zlib-compressed
    offset 5   <ii>        header length, payload length
    offset 13  header      JSON {capacity, blocks: [{type, dtypes,
                           nwords, has_nulls, dictionary?}], live?}
    13+hlen    payload     one frame per array, in header order:
                           data words, then nulls (if has_nulls) per
                           block, then the page validity mask

When the header carries "live" < capacity, every frame stores only
the first `live` elements (the prefix through the LAST valid row);
the decoder zero/False-fills the dead tail. Rows past the last valid
row are masked out of every consumer, so their backing values are
wire freight with no information — compacted exchange partitions
with a short live prefix shed most of their bytes here, and the
truncation also removes the live-data -> zero-padding cliff that
would otherwise blow the delta codec's narrow width.

Frame = codec byte | <q> stored length | stored bytes. The codec byte
is `base | 0x80` when the stored bytes are additionally
zlib-compressed (the general compressed fallback). Base codecs (the
BlockEncoding analog):

    0 RAW       full array bytes
    1 RLE       ONE element for a bit-identical constant run
                (reference: spi/block/RunLengthEncodedBlock —
                constant columns, all-false null masks, all-true
                validity masks collapse to one value on the wire;
                constancy is tested on BYTES, so constant-NaN arrays
                collapse and mixed +0.0/-0.0 arrays do not)
    2/3/4 INT8/16/32  narrowest-int downcast of a wider integer
                array whose min/max fit (dictionary code words and
                low-cardinality int64 columns ship 2-8x narrower
                before compression)
    5 BOOLPACK  np.packbits bitmap for boolean arrays (8x)
    6/7/8 DELTA8/16/32  first element full-width + consecutive
                differences downcast to the narrowest signed width
                that fits (differences are taken modulo 2^w, so any
                integer array is representable; the probe only picks
                delta when its stored size beats the plain downcast).
                Scan-ordered key columns (orderkeys, positions)
                delta down to 1 byte/row and then deflate to almost
                nothing — the lever behind the q3-family wire pin.

The codec is chosen per array by a cheap size probe at serialize
time and the choice is DETERMINISTIC, so a replayed or re-fetched
page serializes byte-identically (dist/dcn.py `_prefix_matches`
verifies consumed prefixes by rolling sha256 — the replay contract).
Every frame length is validated against the header's dtype/count on
decode: a truncated or corrupt blob raises PageWireError instead of
np.frombuffer silently reading garbage.

Types are reconstructed by name through presto_tpu.types;
dictionaries ship as JSON value lists (content-equal on arrival —
Dictionary hashes by content).

Wire accounting: serialize_page meters blob bytes (wire) and
pre-codec array bytes (raw) onto module process totals
(`wire_totals()`, overlaid on /metrics + system.metrics like the
exec/xfer.py transfer totals) and onto the thread-bound transfer
sink's registry counters `exchange_wire_bytes`/`exchange_raw_bytes`
(exec/counters.py) when one is installed.
"""

from __future__ import annotations

import json
import struct
import zlib
from typing import Iterator, List

import numpy as np

from presto_tpu import types as T
from presto_tpu.exec import xfer as XF
from presto_tpu.page import Block, Dictionary, Page

_MAGIC = b"PTP"
_VERSION = b"3"
_FLAG_HDR_ZLIB = 0x01

# base codec bytes (low 7 bits); 0x80 flags a zlib-wrapped frame
_RAW = 0
_RLE = 1
_INT8 = 2
_INT16 = 3
_INT32 = 4
_BOOLPACK = 5
_DELTA8 = 6
_DELTA16 = 7
_DELTA32 = 8
_ZLIB_FLAG = 0x80
_DOWNCAST_SIZE = {_INT8: 1, _INT16: 2, _INT32: 4}
_DELTA_SIZE = {_DELTA8: 1, _DELTA16: 2, _DELTA32: 4}

# the general-fallback compression level. The pre-v3 plane shipped
# whole-payload zlib level 1; per-array framing lets the fallback
# afford a denser level because only incompressible-after-codec
# arrays reach it.
_ZLIB_LEVEL = 6
# don't probe zlib below this: the deflate header + probe CPU cannot
# win on tiny frames
_ZLIB_MIN_BYTES = 64

# wire mode: "full" = the v3 per-column codec chooser (default);
# "zlib" = raw/RLE + zlib-only (the pre-ISSUE-16 baseline, kept for
# the measured wire-bytes acceptance pin and A/B grading);
# "raw" = no codecs at all (the uncompressed row-parity reference).
# Mode is process-global: every producer of one exchange must agree,
# and replay determinism holds per mode.
_MODE = "full"

# process-lifetime wire totals (the exec/xfer.py `_totals` pattern:
# monotonically increasing ints, GIL-atomic +=, read by /metrics,
# where per-query executor gauges from worker task threads never
# surface)
_TOTALS = {"exchange_wire_bytes": 0, "exchange_raw_bytes": 0}


class PageWireError(ValueError):
    """A page blob failed structural validation (bad magic/version,
    truncated frame, length/dtype mismatch, corrupt compressed data).
    Pointed and LOUD — the fetch plane treats it as a poisoned blob,
    never as rows."""


def wire_fingerprint() -> str:
    """Identity of the wire serde FORMAT (magic + version) — the
    persistent result-cache manifest records it so a cache directory
    written by one serde version is dropped loudly, not misdecoded,
    by another (cache/persist.py). Mode is deliberately excluded:
    every mode decodes every mode's frames (the codec byte rides in
    each frame), only the encode choice differs."""
    return (_MAGIC + _VERSION).decode("ascii")


def set_wire_mode(mode: str) -> str:
    """Select the wire codec mode ("full" | "zlib" | "raw"); returns
    the previous mode. Test surface for A/B wire-bytes grading
    — production runs stay on "full"."""
    global _MODE
    if mode not in ("full", "zlib", "raw"):
        raise ValueError(f"unknown wire mode {mode!r}")
    prev, _MODE = _MODE, mode
    return prev


def wire_totals() -> dict:
    """Process-lifetime wire byte totals (serialize side), for the
    /metrics + system.metrics overlay."""
    return dict(_TOTALS)


def _count_wire(wire: int, raw: int) -> None:
    _TOTALS["exchange_wire_bytes"] += wire
    _TOTALS["exchange_raw_bytes"] += raw
    sink = XF.current_sink()
    count = getattr(sink, "count_wire", None)
    if count is not None:
        count(wire, raw)


def _type_to_json(t: T.SqlType):
    return t.display()


def _type_from_json(s: str) -> T.SqlType:
    return T.parse_type(s)


def _arrays_of(block: Block) -> List[np.ndarray]:
    datas = block.data if isinstance(block.data, tuple) else (block.data,)
    return [XF.np_host(d) for d in datas]


def _dic_value_to_json(v):
    """Type-preserving dictionary-value encoding: dictionaries hold
    strings, python ints/floats/bools, bytes (varbinary), None, and
    nested tuples (array/map/row values) — str() would corrupt all but
    the first (reference analog: BlockEncoding serde is typed)."""
    if v is None or isinstance(v, (str, bool)):
        return v
    if isinstance(v, (bytes, bytearray)):
        return {"b": bytes(v).hex()}
    if isinstance(v, (int, float)):
        return {"n": v}
    if isinstance(v, (tuple, list)):
        return {"t": [_dic_value_to_json(x) for x in v]}
    return str(v)


def _dic_value_from_json(v):
    if v is None or isinstance(v, (str, bool)):
        return v
    if isinstance(v, dict):
        if "b" in v:
            return bytes.fromhex(v["b"])
        if "n" in v:
            return v["n"]
        if "t" in v:
            return tuple(_dic_value_from_json(x) for x in v["t"])
    return v


# ------------------------------------------------------------ encode
def _is_constant(arr: np.ndarray) -> bool:
    """Bit-identical constant run? Tested on BYTES, not values: NaN
    compares unequal to itself under `==` (the pre-v3 RLE detector
    never collapsed constant-NaN float columns) while -0.0 compares
    EQUAL to +0.0 (value-equality would corrupt the sign bit on the
    wire). A first/last element precheck short-circuits the O(n)
    scan for the common non-constant case."""
    if arr.size <= 1:
        return False
    first = arr[:1].tobytes()
    if arr[-1:].tobytes() != first:
        return False
    return arr.tobytes() == first * arr.size


def _downcast(arr: np.ndarray):
    """Narrowest-int downcast probe: (codec, narrow_array) when the
    array's min/max fit a strictly narrower integer width, else
    None. min/max is the cheap O(n) size probe; the choice is a pure
    function of the data, so re-serialization is byte-stable."""
    kind = arr.dtype.kind
    if kind not in "iu" or arr.dtype.itemsize <= 1 or arr.size == 0:
        return None
    lo = int(arr.min())
    hi = int(arr.max())
    for codec in (_INT8, _INT16, _INT32):
        size = _DOWNCAST_SIZE[codec]
        if size >= arr.dtype.itemsize:
            return None
        info = np.iinfo(f"{kind}{size}")
        if info.min <= lo and hi <= info.max:
            return codec, arr.astype(f"<{kind}{size}")
    return None


def _delta(arr: np.ndarray):
    """Delta-encode probe: (codec, narrow_diff_array) when the
    consecutive differences (taken modulo 2^width, so ANY integer
    array is representable without overflow) fit a strictly narrower
    signed width, else None. Sorted or clustered key columns have
    tiny deltas even when their values need the full width. Like
    _downcast, a pure function of the data — byte-stable."""
    if arr.dtype.kind not in "iu" or arr.dtype.itemsize <= 1 or arr.size < 2:
        return None
    w = arr.dtype.itemsize
    # unsigned view -> wraparound subtract -> reinterpret signed:
    # the modular delta, exact for any input including i64 min->max
    ud = np.diff(arr.view(f"<u{w}"))
    sd = ud.view(f"<i{w}")
    lo = int(sd.min())
    hi = int(sd.max())
    for codec in (_DELTA8, _DELTA16, _DELTA32):
        size = _DELTA_SIZE[codec]
        if size >= w:
            return None
        info = np.iinfo(f"i{size}")
        if info.min <= lo and hi <= info.max:
            return codec, sd.astype(f"<i{size}")
    return None


def _encode_array(arr: np.ndarray, out: bytearray) -> int:
    """Append one frame (codec byte | <q len> | bytes) for `arr`;
    returns the array's raw byte size for wire accounting."""
    arr = np.ascontiguousarray(arr)
    raw = arr.tobytes()
    if _MODE == "raw":
        out.append(_RAW)
        out.extend(struct.pack("<q", len(raw)))
        out.extend(raw)
        return len(raw)

    if _is_constant(arr):
        one = raw[: arr.dtype.itemsize]
        out.append(_RLE)
        out.extend(struct.pack("<q", len(one)))
        out.extend(one)
        return len(raw)

    codec, base = _RAW, raw
    if _MODE == "full":
        if arr.dtype.kind == "b":
            packed = np.packbits(arr.view(np.uint8)).tobytes()
            if len(packed) < len(raw):
                codec, base = _BOOLPACK, packed
        else:
            # size-probe the integer codecs; smallest stored size
            # wins, plain downcast preferred on ties (cheaper decode)
            down = _downcast(arr)
            if down is not None:
                codec, base = down[0], down[1].tobytes()
            delta = _delta(arr)
            if delta is not None:
                dbase = raw[: arr.dtype.itemsize] + delta[1].tobytes()
                if len(dbase) < len(base):
                    codec, base = delta[0], dbase

    # general compressed fallback, chosen by probe: wrap when the
    # deflate stream is strictly smaller (deterministic — zlib at a
    # fixed level is a pure function of its input)
    if len(base) >= _ZLIB_MIN_BYTES:
        level = _ZLIB_LEVEL if _MODE == "full" else 1
        comp = zlib.compress(base, level)
        if len(comp) < len(base):
            out.append(codec | _ZLIB_FLAG)
            out.extend(struct.pack("<q", len(comp)))
            out.extend(comp)
            return len(raw)
    out.append(codec)
    out.extend(struct.pack("<q", len(base)))
    out.extend(base)
    return len(raw)


def serialize_page(page: Page) -> bytes:
    """One Page -> bytes (the SerializedPage analog)."""
    cap = int(page.capacity)
    valid_np = np.ascontiguousarray(XF.np_host(page.valid))
    header = {"capacity": cap, "blocks": []}
    payload = bytearray()
    raw_bytes = 0

    # live-prefix truncation: rows past the LAST valid row are dead
    # in every consumer (masked by `valid`), so ship only the prefix.
    # Raw accounting still counts the full arrays — the wire/raw
    # ratio is "bytes shipped per byte of page".
    live = cap
    if _MODE == "full" and valid_np.size == cap:
        live = (int(cap - np.argmax(valid_np[::-1]))
                if valid_np.any() else 0)
        if live < cap:
            header["live"] = live

    def _enc(a: np.ndarray) -> None:
        nonlocal raw_bytes
        raw_bytes += a.nbytes
        if live < a.shape[0]:
            a = a[:live]
        _encode_array(a, payload)

    for blk in page.blocks:
        arrays = _arrays_of(blk)
        bh = {
            "type": _type_to_json(blk.type),
            "dtypes": [a.dtype.str for a in arrays],
            "nwords": len(arrays),
            "has_nulls": blk.nulls is not None,
            "dictionary": (
                [_dic_value_to_json(v) for v in blk.dictionary.values]
                if blk.dictionary is not None else None
            ),
        }
        header["blocks"].append(bh)
        for a in arrays:
            _enc(a)
        if blk.nulls is not None:
            _enc(XF.np_host(blk.nulls))
    _enc(valid_np)

    hdr = json.dumps(header).encode()
    flags = 0
    if _MODE != "raw" and len(hdr) >= 256:
        # dictionary-heavy headers (varchar columns ship their value
        # lists as JSON) dominate some pages — same probe discipline
        chdr = zlib.compress(hdr, _ZLIB_LEVEL if _MODE == "full" else 1)
        if len(chdr) < len(hdr):
            hdr, flags = chdr, _FLAG_HDR_ZLIB
    blob = (_MAGIC + _VERSION + bytes([flags])
            + struct.pack("<ii", len(hdr), len(payload))
            + hdr + bytes(payload))
    _count_wire(len(blob), raw_bytes)
    return blob


# ------------------------------------------------------------ decode
def _fail(msg: str):
    raise PageWireError(f"page blob: {msg}")


def deserialize_page(buf: bytes) -> Page:
    if len(buf) < 13 or buf[:3] != _MAGIC:
        _fail("bad magic (not a presto-tpu page)")
    if buf[3:4] != _VERSION:
        _fail(f"unsupported wire-format version {buf[3:4]!r} "
              f"(this build speaks {_VERSION!r})")
    flags = buf[4]
    hlen, blen = struct.unpack("<ii", buf[5:13])
    if hlen < 0 or blen < 0 or 13 + hlen + blen > len(buf):
        _fail(f"header/payload lengths ({hlen}, {blen}) overrun the "
              f"{len(buf)}-byte blob")
    hdr = buf[13:13 + hlen]
    if flags & _FLAG_HDR_ZLIB:
        try:
            hdr = zlib.decompress(hdr)
        except zlib.error as e:
            _fail(f"corrupt compressed header: {e}")
    try:
        header = json.loads(hdr.decode())
    except (ValueError, UnicodeDecodeError) as e:
        _fail(f"corrupt header JSON: {e}")
    payload = buf[13 + hlen:13 + hlen + blen]
    pos = 0

    def take(dtype: np.dtype, n: int) -> np.ndarray:
        nonlocal pos
        if pos + 9 > len(payload):
            _fail(f"truncated frame at payload offset {pos}")
        codec = payload[pos]
        (ln,) = struct.unpack_from("<q", payload, pos + 1)
        pos += 9
        if ln < 0 or pos + ln > len(payload):
            _fail(f"frame length {ln} at offset {pos} overruns the "
                  f"{len(payload)}-byte payload")
        data = payload[pos:pos + ln]
        pos += ln
        base = codec & ~_ZLIB_FLAG
        if codec & _ZLIB_FLAG:
            try:
                data = zlib.decompress(data)
            except zlib.error as e:
                _fail(f"corrupt compressed frame (codec {base}): {e}")
        if base == _RAW:
            if len(data) != n * dtype.itemsize:
                _fail(f"raw frame holds {len(data)} bytes, expected "
                      f"{n} x {dtype.itemsize} ({dtype})")
            return np.frombuffer(data, dtype=dtype).copy()
        if base == _RLE:
            if len(data) != dtype.itemsize:
                _fail(f"rle frame holds {len(data)} bytes, expected "
                      f"one {dtype.itemsize}-byte element ({dtype})")
            one = np.frombuffer(data, dtype=dtype)
            # broadcast+copy fills by BIT PATTERN — np.full would
            # round-trip the element through a python scalar, which
            # is lossy for NaN payloads
            return np.broadcast_to(one, (n,)).copy()
        if base in _DOWNCAST_SIZE:
            size = _DOWNCAST_SIZE[base]
            if dtype.kind not in "iu" or size >= dtype.itemsize:
                _fail(f"int{size * 8} downcast frame for "
                      f"non-widening dtype {dtype}")
            if len(data) != n * size:
                _fail(f"int{size * 8} frame holds {len(data)} bytes, "
                      f"expected {n} x {size}")
            narrow = np.frombuffer(data, dtype=f"<{dtype.kind}{size}")
            return narrow.astype(dtype)
        if base in _DELTA_SIZE:
            size = _DELTA_SIZE[base]
            w = dtype.itemsize
            if dtype.kind not in "iu" or size >= w:
                _fail(f"delta{size * 8} frame for non-widening "
                      f"dtype {dtype}")
            want = w + max(n - 1, 0) * size
            if len(data) != want:
                _fail(f"delta{size * 8} frame holds {len(data)} "
                      f"bytes, expected {want} for {n} rows of "
                      f"{dtype}")
            if n == 0:
                return np.empty(0, dtype=dtype)
            first = np.frombuffer(data, dtype=f"<u{w}", count=1)
            sd = np.frombuffer(data, dtype=f"<i{size}", offset=w)
            out = np.empty(n, dtype=f"<u{w}")
            out[0] = first[0]
            if n > 1:
                # sign-extend the narrow deltas, then wraparound
                # prefix-sum — the exact inverse of the modular diff
                np.cumsum(sd.astype(f"<u{w}"), out=out[1:])
                out[1:] += first[0]
            return out.view(dtype)
        if base == _BOOLPACK:
            if dtype.kind != "b":
                _fail(f"boolpack frame for non-bool dtype {dtype}")
            if len(data) != (n + 7) // 8:
                _fail(f"boolpack frame holds {len(data)} bytes, "
                      f"expected {(n + 7) // 8} for {n} rows")
            bits = np.unpackbits(
                np.frombuffer(data, dtype=np.uint8), count=n)
            return bits.astype(np.bool_)
        _fail(f"unknown codec byte {codec:#x}")

    try:
        cap = int(header["capacity"])
        live = int(header.get("live", cap))
        block_headers = header["blocks"]
    except (KeyError, TypeError, ValueError) as e:
        _fail(f"malformed header: {e}")
    if not 0 <= live <= cap:
        _fail(f"live prefix {live} outside page capacity {cap}")

    def pad(a: np.ndarray) -> np.ndarray:
        # zero/False-fill the dead tail dropped by the live-prefix
        # truncation (rows past the last valid row)
        if live == cap:
            return a
        full = np.zeros(cap, dtype=a.dtype)
        full[:live] = a
        return full

    blocks = []
    for bh in block_headers:
        arrays = [pad(take(np.dtype(d), live)) for d in bh["dtypes"]]
        nulls = (pad(take(np.dtype(np.bool_), live))
                 if bh["has_nulls"] else None)
        dic = (
            Dictionary([_dic_value_from_json(v)
                        for v in bh["dictionary"]])
            if bh["dictionary"] is not None else None
        )
        data = tuple(arrays) if bh["nwords"] > 1 else arrays[0]
        blocks.append(Block(
            data=data, type=_type_from_json(bh["type"]), nulls=nulls,
            dictionary=dic,
        ))
    valid = pad(take(np.dtype(np.bool_), live))
    if pos != len(payload):
        _fail(f"{len(payload) - pos} trailing payload bytes after "
              f"the last frame")
    return Page(blocks=tuple(blocks), valid=valid)


def serialize_pages(pages) -> Iterator[bytes]:
    for p in pages:
        yield serialize_page(p)
