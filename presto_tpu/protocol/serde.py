"""SerializedPage wire codec — bit-compatible with Presto's data plane.

Wire layout (little-endian; reference:
presto-spi/.../page/PagesSerdeUtil.java:64-90 write/readSerializedPage):

    positionCount      int32
    pageCodecMarkers   byte   (COMPRESSED=1, ENCRYPTED=2, CHECKSUMMED=4;
                               presto-spi/.../page/PageCodecMarker.java:25)
    uncompressedSize   int32
    sizeInBytes        int32  (length of the payload that follows)
    checksum           int64  (CRC32 of payload+markers+positionCount+
                               uncompressedSize when CHECKSUMMED;
                               PagesSerdeUtil.computeSerializedPageChecksum)
    payload            bytes: int32 numBlocks, then per block a
                       length-prefixed encoding name + encoding body
                       (presto-common/.../block/BlockEncodingManager.java:79,
                       EncoderUtil.encodeNullsAsBits bit-packed null flags)

Block encodings implemented: LONG_ARRAY, INT_ARRAY, SHORT_ARRAY,
BYTE_ARRAY, INT128_ARRAY, VARIABLE_WIDTH, RLE, DICTIONARY (each matching
presto-common/.../block/<Name>BlockEncoding.java). Values live in numpy
arrays; DICTIONARY of VARIABLE_WIDTH maps 1:1 onto this engine's
code+StringDict string columns.

Zero-copy contract (the PageBuffer data plane):

  * encode builds the whole frame in ONE pre-sized allocation
    (`PageBuffer`): `_PageWriter` coalesces small header pieces into
    byte runs and scatters every numpy lane straight into the page
    buffer — one copy per lane, no per-lane `tobytes()` + `extend()`
    pair, with a payload-relative block-offsets table for writev-style
    consumers.
  * decode returns READ-ONLY `np.frombuffer` views over the received
    frame: fixed-width lanes, int128 lanes, nested offsets, dictionary
    ids and a VARIABLE_WIDTH block's end offsets and payload alias the
    frame's memory, and each view's `.base` pins the frame alive as
    long as any decoded block lives. The only sanctioned copies —
    null-mask scatter, decompression, and VARIABLE_WIDTH value slicing
    for a caller that reads `.values` — are counted in
    `page_copy_fallback_total{site}` and still come back read-only.
  * a string dictionary crosses once: a `StringDict` keeps its
    VARIABLE_WIDTH form and a digest of it (`StringDict.wire_form`),
    the digest travels as the DICTIONARY block's instance id, and the
    receiver keeps the decoded dictionary under that id
    (`_DICT_CACHE`). A page's rows then move as integer arrays on both
    sides; `presto_tpu_serde_dictionary_total{side,result}` says how
    often.
  * `analysis/rules.py` (`no-page-copy-in-data-plane`) polices the
    contract: `.tobytes()` / `frombuffer(...).copy()` under `protocol/`
    and `spool/` only at the sanctioned sites in this file.
"""

from __future__ import annotations

import collections
import itertools
import operator
import struct
import threading
import time
import zlib
from typing import List, Optional, Tuple

import numpy as np

from presto_tpu.obs.metrics import counter as _counter, \
    histogram as _histogram
from presto_tpu.utils.tracing import TRACER

COMPRESSED = 1
ENCRYPTED = 2
CHECKSUMMED = 4
#: codec id carried in the marker byte's spare high bits (engine
#: extension; 0 = unmarked legacy frame -> magic-byte sniffing)
_CODEC_SHIFT = 4
_CODEC_BITS = {"zlib": 1 << _CODEC_SHIFT, "gzip": 2 << _CODEC_SHIFT,
               "lz4": 3 << _CODEC_SHIFT}
_CODEC_BY_ID = {1: "zlib", 2: "gzip", 3: "lz4"}

_HEADER = struct.Struct("<ibiiq")

_ZERO_COPY_BYTES = _counter(
    "presto_tpu_page_zero_copy_bytes_total",
    "Page bytes that crossed the data plane without an intermediate "
    "copy (scatter-gathered encode lanes, aliased decode payloads, "
    "spool range reads served as views)")
_COPY_FALLBACK = _counter(
    "presto_tpu_page_copy_fallback_total",
    "Sanctioned data-plane copies by site (null_scatter, decompress, "
    "varwidth)", labelnames=("site",))
_DICTIONARY = _counter(
    "presto_tpu_serde_dictionary_total",
    "String dictionaries that crossed the wire codec: a hit found the "
    "dictionary's wire form (encode) or its decoded words (decode) "
    "kept from an earlier page, a miss built them",
    labelnames=("side", "result"))
_EXCHANGE_PAGE = _counter(
    "presto_tpu_exchange_page_total",
    "Pages of the exchange by where their arrays live: a partition as "
    "the producer built it, a page as the consumer decoded it, a page "
    "as it went into the consumer's fuse. A host page crosses the "
    "exchange without touching the device",
    labelnames=("side", "form"))
_ENCODE_SECONDS = _histogram(
    "presto_tpu_serde_encode_seconds", "Wall time per encode_serialized_page call")
_DECODE_SECONDS = _histogram(
    "presto_tpu_serde_decode_seconds", "Wall time per decode_serialized_page call")


#: a DICTIONARY block's instance id when the sender names none
_NO_ID = (0, 0, 0)


class WireBlock:
    """Decoded block: fixed-width values + null mask, or nested forms."""

    __slots__ = ("encoding", "_values", "nulls", "dictionary", "rle_value",
                 "count", "children", "offsets", "ends", "payload",
                 "instance_id")

    def __init__(self, encoding: str,
                 values: Optional[np.ndarray] = None,
                 nulls: Optional[np.ndarray] = None,
                 dictionary: Optional["WireBlock"] = None,
                 rle_value: Optional["WireBlock"] = None,
                 count: int = 0,
                 children: Optional[List["WireBlock"]] = None,
                 offsets: Optional[np.ndarray] = None,
                 ends: Optional[np.ndarray] = None,
                 payload: Optional[np.ndarray] = None,
                 instance_id: Tuple[int, int, int] = _NO_ID):
        self.encoding = encoding
        self._values = values                # fixed-width lanes
        self.nulls = nulls                   # bool, True = NULL
        # DICTIONARY: ids in values, dictionary block nested, and the
        # sender's (most, least significant bits, sequence) id of it
        self.dictionary = dictionary
        self.instance_id = instance_id
        # RLE: single-position value block + count
        self.rle_value = rle_value
        self.count = count
        # ARRAY: children=[elements]; MAP: children=[keys, values];
        # ROW: children=[field0, field1, ...] — with per-position offsets
        # (n+1 int32, rebased to 0, reference ArrayBlockEncoding.java
        # layout)
        self.children = children
        self.offsets = offsets
        # VARIABLE_WIDTH in array form: int32 end offset per position
        # into the uint8 payload (what the wire holds). A block has this
        # form, or `values` as a dtype=object array of bytes, or both
        self.ends = ends
        self.payload = payload

    @property
    def values(self) -> Optional[np.ndarray]:
        if self._values is None and self.ends is not None:
            self._values = _slice_values(self.ends, self.payload,
                                         self.nulls)
        return self._values

    def __repr__(self) -> str:
        return f"WireBlock({self.encoding}, n={self.position_count})"

    @property
    def position_count(self) -> int:
        if self.encoding == "RLE":
            return self.count
        if self.offsets is not None:
            return len(self.offsets) - 1
        if self._values is None and self.ends is not None:
            return len(self.ends)
        return len(self._values)


def _slices(ends: np.ndarray):
    """The slice of a VARIABLE_WIDTH payload that each position holds."""
    stops = ends.tolist()
    return map(slice, [0] + stops[:-1], stops)


def _slice_values(ends: np.ndarray, payload: np.ndarray,
                  nulls: Optional[np.ndarray]) -> np.ndarray:
    """A VARIABLE_WIDTH block's positions as `bytes` objects (None where
    null): one object per position, so only for a caller that reads
    single values — a constant, an RLE value, a golden-bytes test. The
    string columns of a page never come this way (`_decode_words`)."""
    _COPY_FALLBACK.inc(site="varwidth")
    vals = np.empty(len(ends), dtype=object)
    vals[:] = list(map(payload.tobytes().__getitem__, _slices(ends)))
    if nulls is not None:
        vals[nulls] = None
    vals.setflags(write=False)
    return vals


class PageBuffer:
    """One page, one allocation: the full encoded frame (21-byte header
    + payload) in a single pre-sized buffer plus a payload-relative
    offsets table locating each block. This is the unit of zero-copy
    ownership: exchange, spool and the fragment cache can emit
    `memoryview(page_buffer.buffer)` (or the per-block slices the
    offsets table yields) without reassembling bytes; `to_bytes()` is
    the one sanctioned copy out, for callers that hash or key frames."""

    __slots__ = ("buffer", "block_offsets", "position_count")

    def __init__(self, buffer: bytearray, block_offsets: Tuple[int, ...],
                 position_count: int):
        self.buffer = buffer
        self.block_offsets = block_offsets
        self.position_count = position_count

    def __len__(self) -> int:
        return len(self.buffer)

    def view(self) -> memoryview:
        return memoryview(self.buffer)

    def to_bytes(self) -> bytes:
        return bytes(self.buffer)


class _PageWriter:
    """Scatter-gather payload builder. Small struct-packed pieces
    coalesce into pending byte runs; numpy lanes are recorded by
    REFERENCE and written straight into the single page buffer at
    emission time (`write_into`) — the writev analogue of the reference
    native worker's serializer. Exactly one copy per lane."""

    #: lanes under this many bytes ride the coalesced byte run — a
    #: part-table entry costs more than the copy it saves (this
    #: `tobytes()` is a sanctioned site of no-page-copy-in-data-plane)
    _SMALL = 64

    __slots__ = ("_parts", "_pending", "_size", "array_bytes")

    def __init__(self):
        self._parts: List[Tuple[int, object]] = []
        self._pending = bytearray()
        self._size = 0
        self.array_bytes = 0       # bytes scatter-gathered, not copied

    @property
    def size(self) -> int:
        return self._size

    def put(self, piece: bytes):
        self._pending += piece
        self._size += len(piece)

    def put_bytes(self, piece: bytes):
        """A pre-built byte string; large ones are emitted by reference."""
        if len(piece) < self._SMALL:
            self.put(piece)
            return
        self._flush()
        self._parts.append((self._size, piece))
        self._size += len(piece)

    def put_array(self, a: np.ndarray):
        a = np.ascontiguousarray(a)
        if a.nbytes < self._SMALL:
            self.put(a.tobytes())
            return
        self._flush()
        self._parts.append((self._size, a))
        self._size += a.nbytes
        self.array_bytes += a.nbytes

    def _flush(self):
        if self._pending:
            self._parts.append(
                (self._size - len(self._pending), bytes(self._pending)))
            self._pending = bytearray()

    def write_into(self, mv: memoryview, base: int):
        """Scatter every recorded part into `mv` at `base` + offset."""
        self._flush()
        for off, part in self._parts:
            o = base + off
            if isinstance(part, np.ndarray):
                dst = np.frombuffer(mv, dtype=part.dtype,
                                    count=part.size, offset=o)
                dst.reshape(part.shape)[...] = part
            else:
                mv[o:o + len(part)] = part


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def _encode_nulls(w: _PageWriter, nulls: Optional[np.ndarray], n: int):
    """EncoderUtil.encodeNullsAsBits: hasNulls byte then MSB-first bits.
    Uses the native (C++) packer when available (presto_tpu/native)."""
    if nulls is None or not nulls.any():
        w.put(b"\x00")
        return
    w.put(b"\x01")
    from presto_tpu import native
    packed = native.pack_nulls(np.asarray(nulls[:n]))
    if packed is not None:
        w.put_bytes(packed)
        return
    w.put_array(np.packbits(nulls[:n].astype(np.uint8)))  # MSB-first


def _decode_nulls(buf: memoryview, off: int, n: int
                  ) -> Tuple[Optional[np.ndarray], int]:
    has = buf[off]
    off += 1
    if not has:
        return None, off
    nbytes = (n + 7) // 8
    from presto_tpu import native
    nulls = native.unpack_nulls(buf[off:off + nbytes], n)
    if nulls is None:
        bits = np.frombuffer(buf, dtype=np.uint8, count=nbytes,
                             offset=off)
        nulls = np.unpackbits(bits, count=n).astype(bool)
    nulls.setflags(write=False)
    return nulls, off + nbytes


def _view(buf: memoryview, off: int, dtype, count: int) -> np.ndarray:
    """A read-only numpy view over `count` items of `buf` at `off`; the
    view's .base pins the frame buffer alive (zero-copy decode)."""
    return np.frombuffer(buf, dtype=dtype, count=count, offset=off)


def _fixed_width_encode(w: _PageWriter, b: WireBlock, dtype, width: int):
    n = len(b.values)
    w.put(struct.pack("<i", n))
    _encode_nulls(w, b.nulls, n)
    vals = np.ascontiguousarray(b.values, dtype=dtype)
    if b.nulls is not None and b.nulls.any():
        vals = vals[~b.nulls]          # Java writes only non-null slots
    w.put_array(vals)


def _fixed_width_decode(buf: memoryview, off: int, dtype, width: int
                        ) -> Tuple[WireBlock, int]:
    (n,) = struct.unpack_from("<i", buf, off)
    off += 4
    nulls, off = _decode_nulls(buf, off, n)
    if nulls is None:
        vals = _view(buf, off, dtype, n)
        off += n * width
    else:
        # null scatter — the wire carries only non-null slots, so the
        # full lane must be rebuilt (sanctioned copy)
        k = int((~nulls).sum())
        packed = _view(buf, off, dtype, k)
        off += k * width
        vals = np.zeros(n, dtype=dtype)
        vals[~nulls] = packed
        vals.setflags(write=False)
        _COPY_FALLBACK.inc(site="null_scatter")
    return WireBlock("", vals, nulls), off


# ---------------------------------------------------------------------------
# per-encoding codecs
# ---------------------------------------------------------------------------

_FIXED = {"LONG_ARRAY": (np.int64, 8), "INT_ARRAY": (np.int32, 4),
          "SHORT_ARRAY": (np.int16, 2), "BYTE_ARRAY": (np.uint8, 1)}


def _encode_block(w: _PageWriter, b: WireBlock):
    name = b.encoding.encode()
    w.put(struct.pack("<i", len(name)))
    w.put(name)
    if b.encoding in _FIXED:
        dtype, width = _FIXED[b.encoding]
        _fixed_width_encode(w, b, dtype, width)
    elif b.encoding == "INT128_ARRAY":
        # two int64 lanes per position (values shape [n, 2]: low, high)
        n = len(b.values)
        w.put(struct.pack("<i", n))
        _encode_nulls(w, b.nulls, n)
        vals = np.ascontiguousarray(b.values, dtype=np.int64)
        if b.nulls is not None and b.nulls.any():
            vals = vals[~b.nulls]
        w.put_array(vals)
    elif b.encoding == "VARIABLE_WIDTH":
        if b.ends is not None:
            # the array form goes out as it is: no bytes objects
            n = len(b.ends)
            ends, payload = b.ends, b.payload
        else:
            n = len(b.values)
            lens = np.array([0 if v is None else len(v) for v in b.values],
                            dtype=np.int64)
            ends = np.cumsum(lens).astype(np.int32)
            payload = b"".join(v for v in b.values if v is not None)
        w.put(struct.pack("<i", n))
        w.put_array(ends)
        _encode_nulls(w, b.nulls, n)
        w.put(struct.pack("<i", len(payload)))
        if isinstance(payload, np.ndarray):
            w.put_array(payload)
        else:
            w.put_bytes(payload)
    elif b.encoding == "ARRAY":
        # reference ArrayBlockEncoding.java: elements block, then
        # positionCount, offsets[n+1] rebased to 0, null bits
        n = b.position_count
        _encode_block(w, b.children[0])
        w.put(struct.pack("<i", n))
        w.put_array(np.ascontiguousarray(b.offsets, dtype=np.int32))
        _encode_nulls(w, b.nulls, n)
    elif b.encoding == "MAP":
        # reference MapBlockEncoding.java: key block, value block,
        # hashtable length (-1 = absent; readers rebuild lazily),
        # positionCount, offsets[n+1], null bits
        n = b.position_count
        _encode_block(w, b.children[0])
        _encode_block(w, b.children[1])
        w.put(struct.pack("<i", -1))
        w.put(struct.pack("<i", n))
        w.put_array(np.ascontiguousarray(b.offsets, dtype=np.int32))
        _encode_nulls(w, b.nulls, n)
    elif b.encoding == "ROW":
        # reference RowBlockEncoding.java: numFields, field blocks,
        # positionCount, fieldBlockOffsets[n+1], null bits
        n = b.position_count
        w.put(struct.pack("<i", len(b.children)))
        for child in b.children:
            _encode_block(w, child)
        w.put(struct.pack("<i", n))
        w.put_array(np.ascontiguousarray(b.offsets, dtype=np.int32))
        _encode_nulls(w, b.nulls, n)
    elif b.encoding == "RLE":
        w.put(struct.pack("<i", b.count))
        _encode_block(w, b.rle_value)
    elif b.encoding == "DICTIONARY":
        n = len(b.values)
        w.put(struct.pack("<i", n))
        _encode_block(w, b.dictionary)
        w.put_array(np.ascontiguousarray(b.values, dtype=np.int32))
        # dictionary instance id (most/least significant bits, sequence);
        # receivers only use it for caching: a digest of the dictionary
        # block where the sender has one (`_flat_to_wire`), else zeros
        w.put(struct.pack("<qqq", *b.instance_id))
    else:
        raise ValueError(f"unsupported encoding {b.encoding}")


def _decode_block(buf: memoryview, off: int) -> Tuple[WireBlock, int]:
    (name_len,) = struct.unpack_from("<i", buf, off)
    off += 4
    name = bytes(buf[off:off + name_len]).decode()
    off += name_len
    if name in _FIXED:
        dtype, width = _FIXED[name]
        b, off = _fixed_width_decode(buf, off, dtype, width)
        b.encoding = name
        return b, off
    if name == "INT128_ARRAY":
        (n,) = struct.unpack_from("<i", buf, off)
        off += 4
        nulls, off = _decode_nulls(buf, off, n)
        if nulls is None:
            vals = _view(buf, off, np.int64, 2 * n).reshape(n, 2)
            off += n * 16
            return WireBlock(name, vals, None), off
        k = int((~nulls).sum())
        packed = _view(buf, off, np.int64, 2 * k).reshape(k, 2)
        off += k * 16
        vals = np.zeros((n, 2), dtype=np.int64)
        vals[~nulls] = packed
        vals.setflags(write=False)
        _COPY_FALLBACK.inc(site="null_scatter")
        return WireBlock(name, vals, nulls), off
    if name == "VARIABLE_WIDTH":
        (n,) = struct.unpack_from("<i", buf, off)
        off += 4
        ends = _view(buf, off, np.int32, n)
        off += 4 * n
        nulls, off = _decode_nulls(buf, off, n)
        (total,) = struct.unpack_from("<i", buf, off)
        off += 4
        # views: per-value bytes objects are made only for a caller
        # that reads `.values` (WireBlock.values)
        payload = _view(buf, off, np.uint8, total)
        off += total
        return WireBlock(name, nulls=nulls, ends=ends,
                         payload=payload), off
    if name == "ARRAY":
        elements, off = _decode_block(buf, off)
        (n,) = struct.unpack_from("<i", buf, off)
        off += 4
        offsets = _view(buf, off, np.int32, n + 1)
        off += 4 * (n + 1)
        nulls, off = _decode_nulls(buf, off, n)
        return WireBlock("ARRAY", nulls=nulls, children=[elements],
                         offsets=offsets), off
    if name == "MAP":
        keys, off = _decode_block(buf, off)
        vals, off = _decode_block(buf, off)
        (ht_len,) = struct.unpack_from("<i", buf, off)
        off += 4
        if ht_len >= 0:          # reader-side lookup index — not needed
            off += 4 * ht_len
        (n,) = struct.unpack_from("<i", buf, off)
        off += 4
        offsets = _view(buf, off, np.int32, n + 1)
        off += 4 * (n + 1)
        nulls, off = _decode_nulls(buf, off, n)
        return WireBlock("MAP", nulls=nulls, children=[keys, vals],
                         offsets=offsets), off
    if name == "ROW":
        (nf,) = struct.unpack_from("<i", buf, off)
        off += 4
        fields = []
        for _ in range(nf):
            f, off = _decode_block(buf, off)
            fields.append(f)
        (n,) = struct.unpack_from("<i", buf, off)
        off += 4
        offsets = _view(buf, off, np.int32, n + 1)
        off += 4 * (n + 1)
        nulls, off = _decode_nulls(buf, off, n)
        return WireBlock("ROW", nulls=nulls, children=fields,
                         offsets=offsets), off
    if name == "RLE":
        (count,) = struct.unpack_from("<i", buf, off)
        off += 4
        inner, off = _decode_block(buf, off)
        return WireBlock("RLE", rle_value=inner, count=count), off
    if name == "DICTIONARY":
        (n,) = struct.unpack_from("<i", buf, off)
        off += 4
        dictionary, off = _decode_block(buf, off)
        ids = _view(buf, off, np.int32, n)
        off += 4 * n
        instance_id = struct.unpack_from("<qqq", buf, off)
        off += 24
        return WireBlock("DICTIONARY", ids, None, dictionary=dictionary,
                         instance_id=instance_id), off
    raise ValueError(f"unsupported encoding {name}")


# ---------------------------------------------------------------------------
# page level
# ---------------------------------------------------------------------------

def _checksum_tail(crc: int, markers: int, position_count: int,
                   uncompressed: int) -> int:
    """Chain the header fields onto a payload CRC (Java updateCrc order:
    markers byte, positionCount, uncompressedSize, little-endian)."""
    tail = bytes([markers & 0xFF]) + struct.pack("<i", position_count) \
        + struct.pack("<i", uncompressed)
    return zlib.crc32(tail, crc)


def _checksum(payload, markers: int, position_count: int,
              uncompressed: int) -> int:
    # the native slice-by-8 CRC outruns zlib's on this image; both
    # compute the same reflected-poly value java.util.zip.CRC32 does
    from presto_tpu import native
    crc = native.crc32(payload)
    if crc is None:
        crc = zlib.crc32(payload)
    return _checksum_tail(crc, markers, position_count, uncompressed)


def encode_page_buffer(blocks: List[WireBlock],
                       checksummed: bool = True,
                       compression: Optional[str] = None) -> PageBuffer:
    """Encode a page into ONE pre-sized allocation (see `PageBuffer`)."""
    if not blocks:
        raise ValueError("page needs at least one block")
    t0 = time.perf_counter()
    position_count = blocks[0].position_count
    w = _PageWriter()
    w.put(struct.pack("<i", len(blocks)))
    block_offsets = []
    for b in blocks:
        block_offsets.append(w.size)
        _encode_block(w, b)
    uncompressed = w.size
    markers = CHECKSUMMED if checksummed else 0
    buf = None
    comp_crc = None
    if compression in ("zlib", "gzip", "lz4") and uncompressed > 256:
        raw = bytearray(uncompressed)
        w.write_into(memoryview(raw), 0)
        comp = None
        if compression == "lz4" and checksummed:
            # native fused path: compress + CRC the transmitted payload
            # in one call (frame CRC fast path, native/page_codec.cc)
            from presto_tpu import native
            pair = native.lz4_compress_crc(raw)
            if pair is not None:
                comp, comp_crc = pair
        if comp is None:
            comp = _compress(raw, compression)
        if comp is not None and len(comp) < uncompressed:
            buf = bytearray(21 + len(comp))
            buf[21:] = comp
            # codec id in the marker byte's spare bits (above
            # COMPRESSED/ENCRYPTED/CHECKSUMMED) so the consumer decodes
            # deterministically instead of sniffing magic bytes — an
            # LZ4 block can begin with zlib's 0x78
            markers |= COMPRESSED | _CODEC_BITS[compression]
        else:
            buf = bytearray(21 + uncompressed)
            buf[21:] = raw             # keep raw when incompressible
            comp_crc = None
    elif compression not in (None, "none", "zlib", "gzip", "lz4"):
        raise ValueError(f"unsupported exchange compression "
                         f"{compression!r}")
    if buf is None:
        buf = bytearray(21 + uncompressed)
        w.write_into(memoryview(buf), 21)
    # checksum covers the payload AS TRANSMITTED
    # (PagesSerdeUtil.computeSerializedPageChecksum)
    checksum = 0
    if checksummed:
        if comp_crc is not None:
            checksum = _checksum_tail(comp_crc, markers, position_count,
                                      uncompressed)
        else:
            checksum = _checksum(memoryview(buf)[21:], markers,
                                 position_count, uncompressed)
    _HEADER.pack_into(buf, 0, position_count, markers, uncompressed,
                      len(buf) - 21, checksum)
    _ZERO_COPY_BYTES.inc(w.array_bytes)
    _ENCODE_SECONDS.observe(time.perf_counter() - t0)
    return PageBuffer(buf, tuple(block_offsets), position_count)


def encode_serialized_page(blocks: List[WireBlock],
                           checksummed: bool = True,
                           compression: Optional[str] = None) -> bytes:
    return encode_page_buffer(blocks, checksummed,
                              compression).to_bytes()


def _compress(payload, codec: str):
    """Compress per the session codec (CompressionCodec.java:16 — the
    reference offers GZIP/LZ4/ZSTD next to NONE). LZ4 block format runs
    in the native C++ layer (native/page_codec.cc); zstd has no library
    in this image and is rejected at the session-property level."""
    if codec == "zlib":
        return zlib.compress(bytes(payload), 6)
    if codec == "gzip":
        co = zlib.compressobj(6, zlib.DEFLATED, 31)   # gzip wrapper
        return co.compress(bytes(payload)) + co.flush()
    # lz4 block
    from presto_tpu import native
    out = native.lz4_compress(payload)
    if out is None:
        raise ValueError(
            "lz4 codec requires the native page codec library")
    return out


def _decompress(payload, uncompressed: int,
                codec: Optional[str] = None) -> bytes:
    """Deterministic decode when the frame's marker bits name the codec;
    magic-byte sniffing (zlib/gzip by magic, LZ4 block fallback) only
    for unmarked legacy frames — every path is validated against the
    frame's declared uncompressed size afterwards."""
    if codec == "zlib":
        return zlib.decompress(payload)
    if codec == "gzip":
        return zlib.decompress(payload, 31)
    if codec == "lz4":
        from presto_tpu import native
        out = native.lz4_decompress(payload, uncompressed)
        if out is None:
            raise ValueError("lz4 frame but no native codec library")
        return out
    if len(payload) >= 2 and payload[0] == 0x78:
        try:
            return zlib.decompress(payload)
        except zlib.error:
            pass                       # an LZ4 block may start 0x78
    if len(payload) >= 2 and payload[0] == 0x1F and payload[1] == 0x8B:
        try:
            return zlib.decompress(payload, 31)
        except zlib.error:
            pass                   # an LZ4 block may start 0x1F 0x8B too
    from presto_tpu import native
    out = native.lz4_decompress(payload, uncompressed)
    if out is None:
        raise ValueError("cannot decompress page (unknown codec or "
                         "native library unavailable)")
    return out


def decode_serialized_page(data, offset: int = 0
                           ) -> Tuple[List[WireBlock], int, int]:
    """Returns (blocks, position_count, next_offset). Decoded lanes are
    READ-ONLY views aliasing `data` (zero-copy; writing raises) — the
    views' .base keeps the frame buffer alive with the page."""
    t0 = time.perf_counter()
    position_count, markers, uncompressed, size, checksum = \
        _HEADER.unpack_from(data, offset)
    off = offset + 21
    mv = memoryview(data)
    if not mv.readonly:
        mv = mv.toreadonly()
    payload = mv[off:off + size]
    if markers & ENCRYPTED:
        raise NotImplementedError("encrypted pages")
    if markers & CHECKSUMMED:
        want = _checksum(payload, markers, position_count, uncompressed)
        if want != checksum:
            raise ValueError(f"page checksum mismatch: {want} != {checksum}")
    if markers & COMPRESSED:
        codec = _CODEC_BY_ID.get((markers >> _CODEC_SHIFT) & 0x3)
        payload = memoryview(_decompress(payload, uncompressed, codec))
        _COPY_FALLBACK.inc(site="decompress")
        if len(payload) != uncompressed:
            raise ValueError(
                f"decompressed size {len(payload)} != declared "
                f"{uncompressed}")
    else:
        _ZERO_COPY_BYTES.inc(size)
    (nblocks,) = struct.unpack_from("<i", payload, 0)
    p = 4
    blocks = []
    for _ in range(nblocks):
        b, p = _decode_block(payload, p)
        blocks.append(b)
    _DECODE_SECONDS.observe(time.perf_counter() - t0)
    return blocks, position_count, off + size


# ---------------------------------------------------------------------------
# engine Page <-> wire blocks
# ---------------------------------------------------------------------------

def _flat_to_wire(t, vals: np.ndarray, nulls: np.ndarray,
                  dictionary) -> WireBlock:
    if t.is_string and dictionary is not None:
        _note_dictionary("encode", dictionary.has_wire_form)
        ends, payload, (most, least) = dictionary.wire_form()
        # every frame carries its dictionary's words, kept form or not
        TRACER.add("serialize", dict_words=len(ends))
        ids = np.where(nulls, 0, vals).astype(np.int32)
        slot_nulls, sequence = None, 0
        # Presto represents a null string position as a null slot in
        # the dictionary; simplest faithful form: append a null slot
        # (zero bytes long) to the kept arrays. The id's sequence tells
        # that dictionary from the plain one.
        if nulls.any():
            null_slot = len(ends)
            ends = np.append(ends, ends[-1])
            slot_nulls = np.arange(null_slot + 1) == null_slot
            ids = np.where(nulls, null_slot, ids).astype(np.int32)
            sequence = 1
        return WireBlock(
            "DICTIONARY", ids, None,
            dictionary=WireBlock("VARIABLE_WIDTH", nulls=slot_nulls,
                                 ends=ends, payload=payload),
            instance_id=(most, least, sequence))
    if t.dtype == np.bool_:
        return WireBlock("BYTE_ARRAY", vals.astype(np.uint8),
                         nulls if nulls.any() else None)
    if t.dtype == np.int32:
        return WireBlock("INT_ARRAY", vals.astype(np.int32),
                         nulls if nulls.any() else None)
    if t.dtype == np.int64:
        return WireBlock("LONG_ARRAY", vals.astype(np.int64),
                         nulls if nulls.any() else None)
    if t.dtype == np.float64:
        return WireBlock("LONG_ARRAY", vals.view(np.int64),
                         nulls if nulls.any() else None)
    if t.dtype == np.float32:
        return WireBlock("INT_ARRAY", vals.view(np.int32),
                         nulls if nulls.any() else None)
    raise NotImplementedError(f"wire type {t}")


def _any_to_wire(col, idx: np.ndarray) -> WireBlock:
    """Column/NestedColumn rows at absolute positions `idx` -> WireBlock."""
    from presto_tpu.data.column import NestedColumn
    if isinstance(col, NestedColumn):
        return _nested_to_wire(col, idx)
    v, nl = col.to_numpy()
    return _flat_to_wire(col.type, v[idx], nl[idx].copy(),
                         col.dictionary)


def _nested_to_wire(col, idx: np.ndarray) -> WireBlock:
    """NestedColumn rows at `idx` -> ARRAY/MAP/ROW WireBlock with
    contiguous rebased offsets (the reference encodings' region form)."""
    starts = np.asarray(col.starts)[idx]
    lengths = np.asarray(col.lengths)[idx]
    nulls = np.asarray(col.nulls)[idx].copy()
    t = col.type
    if t.name == "row":
        # field entries exist only for non-null rows; offsets advance
        # by 1 per non-null row (createRowBlockInternal semantics)
        keep = ~nulls
        fidx = starts[keep]
        children = [_any_to_wire(ch, fidx) for ch in col.children]
        offsets = np.zeros(len(idx) + 1, np.int32)
        offsets[1:] = np.cumsum(keep)
        return WireBlock("ROW", nulls=nulls if nulls.any() else None,
                         children=children, offsets=offsets)
    lens = np.where(nulls, 0, lengths).astype(np.int64)
    eidx = (np.concatenate(
        [np.arange(s, s + ln) for s, ln in zip(starts, lens)])
        if len(idx) else np.zeros(0, np.int64)).astype(np.int64)
    offsets = np.zeros(len(idx) + 1, np.int32)
    offsets[1:] = np.cumsum(lens)
    children = [_any_to_wire(ch, eidx) for ch in col.children]
    return WireBlock("ARRAY" if t.name == "array" else "MAP",
                     nulls=nulls if nulls.any() else None,
                     children=children, offsets=offsets)


def page_to_wire_blocks(page) -> List[WireBlock]:
    """Host-side conversion of an engine Page (presto_tpu.data.column) to
    wire blocks. Strings become DICTIONARY over VARIABLE_WIDTH (the engine's
    native layout); DECIMAL<=18 travels as LONG_ARRAY (short decimal),
    matching Presto's representation; ARRAY/MAP/ROW nest recursively."""
    from presto_tpu.data.column import NestedColumn

    from presto_tpu.data.column import Decimal128Column

    n = int(page.num_rows)
    out: List[WireBlock] = []
    for c in page.columns:
        if isinstance(c, NestedColumn):
            out.append(_nested_to_wire(c, np.arange(n)))
            continue
        if isinstance(c, Decimal128Column):
            # exact recombination -> INT128_ARRAY (low64, high64) lanes;
            # avg forms pre-divide host-side so the wire carries the
            # final value (long-decimal wire layout, Decimals.java)
            lanes = np.zeros((n, 2), dtype=np.int64)
            nulls = np.asarray(c.nulls)[:n].copy()
            scale = c.type.scale
            from presto_tpu.data.column import DEC_CTX
            for i in range(n):
                if nulls[i]:
                    continue
                if c.count is None:
                    # pure-int path, no Decimal context involved at all
                    unscaled = c.unscaled_at(i)
                else:
                    v = c.value_at(i)   # avg pre-divides host-side
                    unscaled = (int(DEC_CTX.scaleb(v, scale)) if scale
                                else int(v))
                lanes[i, 0] = (unscaled & ((1 << 64) - 1)) - (
                    1 << 64 if unscaled & (1 << 63) else 0)
                lanes[i, 1] = unscaled >> 64
            out.append(WireBlock("INT128_ARRAY", lanes,
                                 nulls if nulls.any() else None))
            continue
        vals, nulls = c.to_numpy(n)
        out.append(_flat_to_wire(c.type, vals, nulls.copy(),
                                 c.dictionary))
    return out


def _host_column(t, vals: np.ndarray, nulls: np.ndarray, capacity: int,
                 dictionary=None):
    """Decoded values (of `t`'s dtype) and null flags as a host Column
    under `Column.host_from_numpy`'s rules. Where they fill `capacity`
    (a page of the exchange is decoded at its rows) the arrays are the
    column, views of the frame included; else copies padded to it."""
    from presto_tpu.data.column import Column, fuse_lanes, null_sentinels

    vals = null_sentinels(vals, nulls, t)
    if len(vals) != capacity:
        vals = fuse_lanes([vals], capacity, vals.dtype,
                          t.dtype.type(t.null_sentinel()))
        nulls = fuse_lanes([nulls], capacity, np.bool_, True)
    return Column(vals, nulls, t, dictionary)


def _wire_to_column(b: WireBlock, t, position_count: int, capacity: int,
                    compact_strings: bool = True):
    """One wire block -> engine Column/NestedColumn of type t, over
    numpy arrays: nothing here touches the device."""
    from presto_tpu.data.column import NestedColumn, \
        bucket_capacity, compact_string_dict

    b = _materialize_rle(b)
    if b.encoding in ("ARRAY", "MAP", "ROW"):
        n = position_count
        offs = np.asarray(b.offsets, np.int32)
        nulls = (b.nulls if b.nulls is not None
                 else np.zeros(n, dtype=bool))
        starts = offs[:-1].copy()
        lengths = np.diff(offs).astype(np.int32)
        if b.encoding == "ROW":
            lengths = np.where(nulls[:n], 0, 1).astype(np.int32)
        child_types = (
            (t.element,) if t.name == "array" else
            (t.key, t.value) if t.name == "map" else t.field_types)
        n_child = int(offs[-1]) if len(offs) else 0
        ccap = bucket_capacity(max(n_child, 1))
        children = tuple(
            _wire_to_column(cb, ct, n_child, ccap, compact_strings)
            for cb, ct in zip(b.children, child_types))
        pad = capacity - n
        return NestedColumn(
            np.pad(starts, (0, pad)), np.pad(lengths, (0, pad)),
            np.pad(nulls[:n], (0, pad), constant_values=True),
            children, t)
    if b.encoding == "INT128_ARRAY" and getattr(t, "uses_int128", False):
        from presto_tpu.data.column import Decimal128Column
        n = position_count
        nulls = (b.nulls if b.nulls is not None
                 else np.zeros(n, dtype=bool))
        ints = []
        for i in range(n):
            if bool(nulls[i]):
                ints.append(None)
                continue
            low = int(b.values[i, 0]) & ((1 << 64) - 1)
            ints.append((int(b.values[i, 1]) << 64) | low)
        return Decimal128Column.host_from_unscaled_ints(
            ints, t, capacity=capacity)
    if t.is_string:
        dictionary, codes, nulls = _block_to_strings(b)
        if compact_strings:
            dictionary, codes = compact_string_dict(dictionary, codes,
                                                    nulls)
        return _host_column(t, codes, nulls, capacity, dictionary)
    vals = b.values
    nulls = b.nulls if b.nulls is not None else \
        np.zeros(position_count, dtype=bool)
    if t.dtype == np.float64:
        vals = vals.view(np.float64)
    elif t.dtype == np.float32:
        vals = vals.astype(np.int32).view(np.float32)
    elif t.dtype == np.bool_:
        vals = vals.astype(bool)
    else:
        vals = vals.astype(t.dtype, copy=False)
    return _host_column(t, vals, nulls, capacity)


def wire_blocks_to_page(blocks: List[WireBlock], types, position_count: int,
                        capacity: Optional[int] = None,
                        compact_strings: bool = True, host: bool = False):
    """Wire blocks -> engine Page, on the device: what a program takes
    (`exec/spill` reads a spilled page straight back into one). `types`
    are presto_tpu SQL types. A string column comes with a sorted
    dictionary of exactly the words its rows use.

    The exchange's decoder (`exchange_client.decode_pages`) passes both
    flags the other way, because its pages go no further than a fuse or
    the root's row reader. `host`: the page stays over numpy arrays,
    its row count too, at exactly its rows (no padding to a bucket: a
    fixed-width column without NULLs is a view of the frame), and the
    fuse (`concat_pages_host`) puts one fused page on the device.
    `compact_strings` False: a string column keeps the dictionary as it
    crossed the wire, marked `sparse`, one object for every page that
    names it, and the fuse compacts once."""
    from presto_tpu.data.column import Page, bucket_capacity, page_to_device

    cap = capacity or (position_count if host
                       else bucket_capacity(max(position_count, 1)))
    cols = [_wire_to_column(b, t, position_count, cap, compact_strings)
            for b, t in zip(blocks, types)]
    page = Page.host_from_columns(cols, position_count)
    return page if host else page_to_device(page)


def _materialize_rle(b: WireBlock) -> WireBlock:
    if b.encoding != "RLE":
        return b
    v = b.rle_value
    n = b.count
    if v.encoding == "VARIABLE_WIDTH":
        # n rows of the one value: a dictionary of it
        return WireBlock("DICTIONARY", np.zeros(n, np.int32),
                         dictionary=v)
    if v.encoding == "DICTIONARY":
        return WireBlock("DICTIONARY", np.repeat(v.values[:1], n),
                         dictionary=v.dictionary,
                         instance_id=v.instance_id)
    vals = np.repeat(v.values[:1], n, axis=0)
    nulls = np.full(n, bool(v.nulls[0]) if v.nulls is not None else False)
    return WireBlock(v.encoding, vals, nulls)


#: the receiver's side of "a dictionary crosses once": decoded
#: dictionaries by the instance id their sender gave them, least
#: recently used out. Bounded by entries and by words held (a word is
#: ~70 bytes of str: 2M words ~ 150 MB); a dictionary larger than the
#: whole budget is decoded for its page and not kept.
_DICT_CACHE_ENTRIES = 64
_DICT_CACHE_WORDS = 2_000_000
_DICT_CACHE: "collections.OrderedDict[Tuple[int, int, int], tuple]" = \
    collections.OrderedDict()
_DICT_CACHE_LOCK = threading.Lock()


def _note_dictionary(side: str, hit: bool) -> None:
    """Count one dictionary through the codec, on the counter and on
    the span around the work (`serialize` / `deserialize`)."""
    _DICTIONARY.inc(side=side, result="hit" if hit else "miss")
    span = "serialize" if side == "encode" else "deserialize"
    if hit:
        TRACER.add(span, dict_hits=1)
    else:
        TRACER.add(span, dict_misses=1)


def note_exchange_pages(side: str, pages) -> int:
    """Count pages of the exchange (`side`: partition, decode, fuse)
    under the form their arrays have, and return how many of the arrays
    live on the device: what the span around the work reports as
    arrays that crossed between host and device."""
    from presto_tpu.data.column import device_leaves

    crossed = 0
    for p in pages:
        k = device_leaves(p)
        _EXCHANGE_PAGE.inc(side=side, form="device" if k else "host")
        crossed += k
    return crossed


def _decode_words(d: WireBlock):
    """A VARIABLE_WIDTH block of k words -> (sorted StringDict, remap,
    isnull): `remap[i]` is the code of wire position i (None for the
    identity), `isnull[i]` whether that position is a null slot (None
    where there is none). Null slots read as "", which the dictionary
    then holds. Once a dictionary: the one place with a pass of Python
    over the words, counted on the `deserialize` span as `dict_words`."""
    from presto_tpu.data.column import StringDict

    if d.encoding != "VARIABLE_WIDTH":
        raise NotImplementedError(f"string dictionary block {d.encoding}")
    k = d.position_count
    TRACER.add("deserialize", dict_words=k)
    if d.ends is not None:
        raw = d.payload.tobytes()
        text = raw.decode()
        if len(text) == len(raw):     # ASCII: byte offsets are characters
            words = list(map(text.__getitem__, _slices(d.ends)))
        else:
            words = [raw[s].decode() for s in _slices(d.ends)]
    else:
        words = [(v or b"").decode() for v in d.values]
    isnull = d.nulls if d.nulls is not None and d.nulls.any() else None
    live = words if isnull is None else \
        list(itertools.compress(words, (~isnull).tolist()))
    if all(map(operator.lt, live, itertools.islice(live, 1, None))):
        # strictly increasing, as the engine's own always are: the wire
        # order is the code order
        if isnull is None:
            return StringDict(live, sparse=True), None, None
        lead = 0 if live and live[0] == "" else 1
        remap = np.zeros(k, np.int32)         # a null slot -> ""
        remap[~isnull] = np.arange(lead, lead + len(live), dtype=np.int32)
        return (StringDict([""] * lead + live, sparse=True), remap,
                isnull)
    # a foreign sender: any order, words may repeat
    uniq, remap = np.unique(np.asarray(words, dtype=object).astype(str),
                            return_inverse=True)
    return (StringDict([str(u) for u in uniq], sparse=True),
            remap.astype(np.int32), isnull)


def _cached_words(d: WireBlock, key: Tuple[int, int, int]):
    """`_decode_words(d)`, kept under the instance id `key` the sender
    gave the dictionary. A zero id names nothing (a foreign frame, a
    frame spooled by an older sender, a hand-built block): decoded for
    its page alone."""
    if key == _NO_ID:
        return _decode_words(d)
    k = d.position_count
    with _DICT_CACHE_LOCK:
        entry = _DICT_CACHE.get(key)
        hit = entry is not None and entry[0] == k
        if hit:
            _DICT_CACHE.move_to_end(key)
    _note_dictionary("decode", hit)
    if hit:
        return entry[1:]
    decoded = _decode_words(d)
    if k <= _DICT_CACHE_WORDS:
        with _DICT_CACHE_LOCK:
            _DICT_CACHE[key] = (k,) + decoded
            _DICT_CACHE.move_to_end(key)
            held = sum(e[0] for e in _DICT_CACHE.values())
            while (len(_DICT_CACHE) > _DICT_CACHE_ENTRIES
                   or held > _DICT_CACHE_WORDS):
                _old, gone = _DICT_CACHE.popitem(last=False)
                held -= gone[0]
    return decoded


def _block_to_strings(b: WireBlock):
    """A string block's rows as (dictionary, codes, nulls): int32 codes
    into a sorted StringDict that may hold words no row uses (`sparse`).
    Arrays only, but for a dictionary seen for the first time."""
    if b.encoding == "DICTIONARY":
        dictionary, remap, isnull = _cached_words(b.dictionary,
                                                  b.instance_id)
        ids = b.values
        codes = ids if remap is None else remap[ids]
        nulls = np.zeros(len(ids), dtype=bool) if isnull is None \
            else isnull[ids]
    elif b.encoding == "VARIABLE_WIDTH":
        # a plain string column: its own dictionary, a word a row
        dictionary, codes, nulls = _decode_words(b)
        if codes is None:
            codes = np.arange(len(dictionary), dtype=np.int32)
        if nulls is None:
            nulls = np.zeros(b.position_count, dtype=bool)
    else:
        raise NotImplementedError(f"string block {b.encoding}")
    return dictionary, codes.astype(np.int32, copy=False), nulls
