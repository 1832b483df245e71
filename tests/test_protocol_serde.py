"""SerializedPage wire-format tests: round trips plus golden bytes checked
against the reference layout (PagesSerdeUtil.java:64, EncoderUtil bit
packing, LongArrayBlockEncoding.java)."""

import struct
import zlib

import numpy as np

from presto_tpu.data.column import Column, Page
from presto_tpu.protocol import (
    WireBlock, decode_serialized_page, encode_serialized_page,
    page_to_wire_blocks, wire_blocks_to_page,
)
from presto_tpu.types import BIGINT, BOOLEAN, DOUBLE, INTEGER, VARCHAR


def rt(blocks):
    data = encode_serialized_page(blocks)
    out, n, end = decode_serialized_page(data)
    assert end == len(data)
    return out, n


def test_golden_long_array_no_nulls():
    b = WireBlock("LONG_ARRAY", np.array([1, 2, 3], dtype=np.int64))
    data = encode_serialized_page([b], checksummed=False)
    pos, markers, unc, size, checksum = struct.unpack_from("<ibiiq", data)
    assert (pos, markers, checksum) == (3, 0, 0)
    payload = data[21:]
    assert unc == size == len(payload)
    # numBlocks, name len, name, positionCount, hasNulls, 3 longs
    want = struct.pack("<i", 1) + struct.pack("<i", 10) + b"LONG_ARRAY" \
        + struct.pack("<i", 3) + b"\x00" \
        + struct.pack("<qqq", 1, 2, 3)
    assert payload == want


def test_golden_null_bits_msb_first():
    vals = np.arange(10, dtype=np.int64)
    nulls = np.zeros(10, dtype=bool)
    nulls[0] = nulls[9] = True
    b = WireBlock("LONG_ARRAY", vals, nulls)
    data = encode_serialized_page([b], checksummed=False)
    payload = data[21:]
    base = 4 + 4 + 10 + 4      # numBlocks, namelen, name, positionCount
    assert payload[base] == 1                   # mayHaveNull
    assert payload[base + 1] == 0b1000_0000     # rows 0-7, MSB first
    assert payload[base + 2] == 0b0100_0000     # rows 8-9 in high bits
    # only the 8 non-null longs follow
    assert len(payload) == base + 3 + 8 * 8


def test_checksum_matches_java_crc():
    b = WireBlock("INT_ARRAY", np.array([7], dtype=np.int32))
    data = encode_serialized_page([b], checksummed=True)
    pos, markers, unc, size, checksum = struct.unpack_from("<ibiiq", data)
    assert markers == 4
    payload = data[21:]
    crc = zlib.crc32(payload)
    crc = zlib.crc32(b"\x04", crc)
    crc = zlib.crc32(struct.pack("<i", 1), crc)
    crc = zlib.crc32(struct.pack("<i", unc), crc)
    assert checksum == crc
    decode_serialized_page(data)  # must not raise


def test_round_trip_all_encodings():
    blocks = [
        WireBlock("LONG_ARRAY", np.array([1, -5, 2**62], dtype=np.int64),
                  np.array([False, True, False])),
        WireBlock("INT_ARRAY", np.array([4, 5, 6], dtype=np.int32)),
        WireBlock("SHORT_ARRAY", np.array([1, 2, 3], dtype=np.int16)),
        WireBlock("BYTE_ARRAY", np.array([1, 0, 1], dtype=np.uint8),
                  np.array([False, False, True])),
        WireBlock("VARIABLE_WIDTH",
                  np.array([b"abc", None, b""], dtype=object),
                  np.array([False, True, False])),
        WireBlock("INT128_ARRAY",
                  np.array([[1, 0], [-2, -1], [7, 8]], dtype=np.int64),
                  np.array([False, True, False])),
    ]
    out, n = rt(blocks)
    assert n == 3
    for a, b in zip(blocks, out):
        assert a.encoding == b.encoding
        if a.encoding == "VARIABLE_WIDTH":
            assert list(a.values) == list(b.values)
        else:
            got = np.where(b.nulls, 0, b.values.T).T if b.nulls is not None \
                else b.values
            want = np.where(a.nulls, 0, a.values.T).T \
                if a.nulls is not None else a.values
            assert np.array_equal(got, want)
        an = a.nulls if a.nulls is not None and a.nulls.any() else None
        bn = b.nulls if b.nulls is not None and b.nulls.any() else None
        assert (an is None) == (bn is None)
        if an is not None:
            assert np.array_equal(an, bn)


def test_rle_and_dictionary_round_trip():
    rle = WireBlock("RLE", rle_value=WireBlock(
        "LONG_ARRAY", np.array([42], dtype=np.int64)), count=5)
    dict_b = WireBlock(
        "DICTIONARY", np.array([0, 1, 0, 2], dtype=np.int32),
        dictionary=WireBlock(
            "VARIABLE_WIDTH",
            np.array([b"x", b"y", b"z"], dtype=object)))
    out, n = rt([rle, dict_b])
    assert out[0].encoding == "RLE" and out[0].count == 5
    assert out[0].rle_value.values[0] == 42
    assert out[1].encoding == "DICTIONARY"
    assert list(out[1].values) == [0, 1, 0, 2]
    assert list(out[1].dictionary.values) == [b"x", b"y", b"z"]


def test_engine_page_round_trip():
    page = Page.from_pydict(
        {"k": [1, 2, None], "name": ["bob", None, "amy"],
         "v": [1.5, None, -2.25], "f": [True, False, None],
         "i": [7, 8, 9]},
        {"k": BIGINT, "name": VARCHAR, "v": DOUBLE, "f": BOOLEAN,
         "i": INTEGER})
    blocks = page_to_wire_blocks(page)
    data = encode_serialized_page(blocks)
    blocks2, n, _ = decode_serialized_page(data)
    page2 = wire_blocks_to_page(blocks2, [BIGINT, VARCHAR, DOUBLE,
                                          BOOLEAN, INTEGER], n)
    assert page2.to_pylist() == page.to_pylist()


def test_native_codec_matches_numpy():
    """The C++ marshalling path (presto_tpu/native) must be bit-identical
    to the numpy fallback: null bitmaps, CRC, and full page frames."""
    import zlib

    import numpy as np

    from presto_tpu import native

    lib = native.load()
    if lib is None:
        import pytest
        pytest.skip("no C++ toolchain available")

    rng = np.random.RandomState(0)
    for n in (1, 7, 8, 9, 1000):
        nulls = rng.rand(n) < 0.3
        packed = native.pack_nulls(nulls)
        assert packed == np.packbits(nulls.astype(np.uint8)).tobytes()
        back = native.unpack_nulls(packed, n)
        assert (back == nulls).all()
    data = rng.bytes(100000)
    assert native.crc32(data) == zlib.crc32(data)
    assert native.crc32(data, 12345) == zlib.crc32(data, 12345)
    assert native.crc32(b"") == zlib.crc32(b"")


# ---------------------------------------------------------------------------
# A string dictionary crosses once, as arrays (PR 30). The plain reference
# below is the decode and the fuse as they were before: a Python string a
# row, a string sort a page, a set union a fuse.
# ---------------------------------------------------------------------------

import pytest  # noqa: E402

from presto_tpu.data.column import (  # noqa: E402
    StringDict, bucket_capacity, concat_pages_host,
)
from presto_tpu.protocol import serde  # noqa: E402


def _ref_block_to_strings(b):
    """The parent's `_block_to_strings`, with its `_materialize_rle`."""
    if b.encoding == "RLE":
        v = b.rle_value
        vals = np.empty(b.count, dtype=object)
        vals[:] = [v.values[0]] * b.count
        b = WireBlock("VARIABLE_WIDTH", vals, np.full(
            b.count, bool(v.nulls[0]) if v.nulls is not None else False))
    if b.encoding == "DICTIONARY":
        d = b.dictionary
        raw = [None if (d.nulls is not None and d.nulls[i]) else
               (d.values[i] or b"").decode() for i in range(len(d.values))]
        strings = [raw[i] for i in b.values]
    else:
        strings = [None if v is None else v.decode() for v in b.values]
    nulls = np.array([s is None for s in strings], dtype=bool)
    filled = ["" if s is None else s for s in strings]
    uniq, codes = np.unique(np.asarray(filled, dtype=object).astype(str),
                            return_inverse=True)
    return [str(u) for u in uniq], codes.astype(np.int32), nulls


def _ref_merge_string_dicts(word_lists):
    """The parent's `merge_string_dicts`, over lists of words."""
    union = sorted(set().union(*[set(w) for w in word_lists]))
    union_arr = np.asarray(union, dtype=object).astype(str)
    remaps = [np.searchsorted(
        union_arr, np.asarray(w, dtype=object).astype(str)).astype(np.int32)
        if w else np.zeros(0, np.int32) for w in word_lists]
    return union, remaps


def _ref_decode_and_fuse(frames):
    """Frames of one VARCHAR column -> what the parent's consumer got:
    per page (words, codes, nulls), and the fused (words, codes, nulls,
    capacity). Null rows hold VARCHAR's sentinel, as in a Column."""
    sentinel = VARCHAR.null_sentinel()
    pages = []
    for data in frames:
        (b,), n, _ = decode_serialized_page(data)
        words, codes, nulls = _ref_block_to_strings(b)
        assert len(codes) == n
        pages.append((words, np.where(nulls, sentinel, codes), nulls))
    union, remaps = _ref_merge_string_dicts([w for w, _c, _n in pages])
    vals = [remap[np.clip(c, 0, len(remap) - 1)] if len(remap) else c
            for (_w, c, _n), remap in zip(pages, remaps)]
    nulls = np.concatenate([n for _w, _c, n in pages])
    codes = np.where(nulls, sentinel, np.concatenate(vals))
    return pages, (union, codes, nulls,
                   bucket_capacity(max(len(codes), 1)))


def _column_parts(page):
    col = page.columns[0]
    n = int(page.num_rows)
    codes, nulls = col.to_numpy(n)
    return list(col.dictionary.words), codes, nulls, col.capacity


def _engine_frame(strings, dictionary=None):
    """One VARCHAR column through the engine's own sender."""
    if dictionary is None:
        page = Page.from_pydict({"s": strings}, {"s": VARCHAR})
    else:
        codes = np.array([0 if s is None else dictionary.code_of(s)
                          for s in strings], dtype=np.int32)
        nulls = np.array([s is None for s in strings], dtype=bool)
        page = Page.from_columns([Column.from_numpy(
            codes, VARCHAR, nulls=nulls, dictionary=dictionary)],
            len(strings))
    return encode_serialized_page(page_to_wire_blocks(page))


def _foreign_frame(words, ids, instance_id, null_slots=()):
    """A DICTIONARY block as another sender may build it: any order,
    repeated words, null slots anywhere, an id of its own choosing."""
    vals = np.array([None if i in null_slots else w.encode()
                     for i, w in enumerate(words)], dtype=object)
    nulls = (np.array([i in null_slots for i in range(len(words))])
             if null_slots else None)
    return encode_serialized_page([WireBlock(
        "DICTIONARY", np.asarray(ids, dtype=np.int32),
        dictionary=WireBlock("VARIABLE_WIDTH", vals, nulls),
        instance_id=instance_id)])


_THOUSAND = StringDict([f"Customer#{i:09d}" for i in range(1000)])


#: name -> the frames of one VARCHAR column, built when the case runs
_STRING_CASES = {
    "engine-sorted": lambda: [_engine_frame(["bob", "amy", "bob", "cat"])],
    "foreign-unsorted-repeated-word": lambda: [_foreign_frame(
        ["pear", "apple", "pear", "fig"], [0, 1, 2, 3, 3, 1],
        (11, 22, 33))],
    "null-slot": lambda: [_engine_frame(["bob", None, "amy", None])],
    "null-slot-beside-the-empty-word": lambda: [_engine_frame(
        ["", None, "amy"])],
    "foreign-null-slot-in-the-middle": lambda: [_foreign_frame(
        ["b", "", "a"], [2, 1, 0, 1], (5, 6, 7), null_slots=(1,))],
    "3-of-1000-words": lambda: [_engine_frame(
        [_THOUSAND[7], _THOUSAND[500], _THOUSAND[7], _THOUSAND[999]],
        _THOUSAND)],
    "zero-instance-id": lambda: [_foreign_frame(
        ["a", "b", "c"], [2, 2, 0], (0, 0, 0))],
    "rle-of-a-string": lambda: [encode_serialized_page([WireBlock(
        "RLE", count=5, rle_value=WireBlock(
            "VARIABLE_WIDTH", np.array([b"same"], dtype=object)))])],
    "rle-of-a-null-string": lambda: [encode_serialized_page([WireBlock(
        "RLE", count=3, rle_value=WireBlock(
            "VARIABLE_WIDTH", np.array([None], dtype=object),
            np.array([True])))])],
    "zero-rows": lambda: [_engine_frame([], _THOUSAND)],
    "plain-variable-width-column": lambda: [encode_serialized_page([
        WireBlock("VARIABLE_WIDTH",
                  np.array([b"z", None, b"a", b"z"], dtype=object),
                  np.array([False, True, False, False]))])],
    "words-beyond-ascii": lambda: [_engine_frame(
        ["żółw", "ab", "日本", "ab"])],
    "two-pages-one-dictionary": lambda: [
        _engine_frame([_THOUSAND[3], _THOUSAND[4]], _THOUSAND),
        _engine_frame([_THOUSAND[4], _THOUSAND[900]], _THOUSAND)],
    "two-pages-different-dictionaries": lambda: [
        _engine_frame(["bob", "amy", None]),
        _engine_frame(["cat", "bob"]),
        _foreign_frame(["zed", "amy"], [0, 1, 0], (1, 2, 3))],
}


@pytest.mark.parametrize("case", sorted(_STRING_CASES))
def test_string_decode_and_fuse_agree_with_the_per_row_reference(case):
    frames = _STRING_CASES[case]()
    ref_pages, ref_fused = _ref_decode_and_fuse(frames)

    # a page on its own (spill, the root): the words present, compacted
    # at the end of wire_blocks_to_page
    for data, (words, codes, nulls) in zip(frames, ref_pages):
        blocks, n, _ = decode_serialized_page(data)
        page = wire_blocks_to_page(blocks, [VARCHAR], n)
        got = _column_parts(page)
        assert got[0] == words
        assert np.array_equal(got[1], codes)
        assert np.array_equal(got[2], nulls)
        assert got[3] == bucket_capacity(max(n, 1))
        assert not page.columns[0].dictionary.sparse

    # the exchange: compaction deferred to the fuse
    pulled = []
    for data in frames:
        blocks, n, _ = decode_serialized_page(data)
        pulled.append(wire_blocks_to_page(blocks, [VARCHAR], n,
                                          compact_strings=False))
    fused = concat_pages_host(pulled)
    words, codes, nulls, capacity = _column_parts(fused)
    assert words == ref_fused[0]
    assert np.array_equal(codes, ref_fused[1])
    assert np.array_equal(nulls, ref_fused[2])
    assert capacity == ref_fused[3]
    assert not fused.columns[0].dictionary.sparse
    # and the rows, word for word
    want_rows = [(None,) if nl else (ref_fused[0][c],)
                 for c, nl in zip(ref_fused[1], ref_fused[2])]
    assert fused.to_pylist() == want_rows


def test_fused_dictionary_holds_only_the_words_in_use():
    """`ops/aggregate._direct_domains` reads `len(c.dictionary)`: a
    fused page that kept the 1,000 words would lower differently."""
    frames = _STRING_CASES["3-of-1000-words"]() * 2
    pulled = []
    for data in frames:
        blocks, n, _ = decode_serialized_page(data)
        pulled.append(wire_blocks_to_page(blocks, [VARCHAR], n,
                                          compact_strings=False))
    assert len(pulled[0].columns[0].dictionary) == 1000
    assert len(concat_pages_host(pulled).columns[0].dictionary) == 3


def test_dictionary_frame_is_the_old_frame_but_for_the_id():
    """Array form against the per-word form (the parent's, still the
    path of a hand-built block): the same bytes outside the 24 bytes of
    the instance id and the header's checksum."""
    strings = ["bob", None, "amy", "żółw", "bob"]
    page = Page.from_pydict({"s": strings}, {"s": VARCHAR})
    (new,) = page_to_wire_blocks(page)
    assert new.instance_id != (0, 0, 0) and new.instance_id[2] == 1
    words = [w.encode() for w in page.columns[0].dictionary.words]
    old = WireBlock(
        "DICTIONARY", new.values, dictionary=WireBlock(
            "VARIABLE_WIDTH", np.array(words + [None], dtype=object),
            np.arange(len(words) + 1) == len(words)))
    got = encode_serialized_page([new])
    want = encode_serialized_page([old])
    assert len(got) == len(want)
    assert got[:13] == want[:13]                 # header up to the checksum
    assert got[21:-24] == want[21:-24]
    assert want[-24:] == bytes(24)
    assert got[-24:] == struct.pack("<qqq", *new.instance_id)
    decode_serialized_page(got)                  # the checksum holds

    # without a null string: no null slot, sequence 0, the same digest
    page = Page.from_pydict({"s": ["bob", "amy"]}, {"s": VARCHAR})
    (plain,) = page_to_wire_blocks(page)
    assert plain.instance_id[2] == 0
    assert plain.dictionary.nulls is None


def _dictionary_count(side, result):
    return serde._DICTIONARY.value(side=side, result=result)


def test_second_page_of_a_dictionary_is_a_hit_on_both_sides():
    d = StringDict([f"w{i:04d}" for i in range(300)])
    before = {(s, r): _dictionary_count(s, r)
              for s in ("encode", "decode") for r in ("hit", "miss")}
    assert not d.has_wire_form
    frames = [_engine_frame([d[1], d[2]], d), _engine_frame([d[299]], d)]
    assert d.has_wire_form
    assert d.wire_form() is d.wire_form()
    pages = []
    for data in frames:
        blocks, n, _ = decode_serialized_page(data)
        pages.append(wire_blocks_to_page(blocks, [VARCHAR], n,
                                         compact_strings=False))
    after = {k: _dictionary_count(*k) for k in before}
    assert {k: after[k] - before[k] for k in before} == {
        ("encode", "miss"): 1, ("encode", "hit"): 1,
        ("decode", "miss"): 1, ("decode", "hit"): 1}
    first, second = (p.columns[0].dictionary for p in pages)
    assert first is second and first.sparse
    assert first.words == d.words
    # the one object fuses without a union, and compacts once
    fused = concat_pages_host(pages)
    assert fused.to_pylist() == [(d[1],), (d[2],), (d[299],)]
    assert fused.columns[0].dictionary.words == (d[1], d[2], d[299])


def test_hits_and_misses_land_on_the_open_span():
    from presto_tpu.utils.tracing import TRACER, trace_scope

    d = StringDict(["only", "these"])
    page = Page.from_columns([Column.from_numpy(
        np.array([0, 1], np.int32), VARCHAR, dictionary=d)], 2)
    with trace_scope("serde-dictionary-test"):
        with TRACER.span(None, "serialize") as out:
            frames = [encode_serialized_page(page_to_wire_blocks(page))
                      for _ in range(3)]
        with TRACER.span(None, "deserialize") as back:
            for data in frames:
                blocks, n, _ = decode_serialized_page(data)
                wire_blocks_to_page(blocks, [VARCHAR], n)
    assert (out.attributes["dict_misses"], out.attributes["dict_hits"]) \
        == (1, 2)
    assert back.attributes["dict_hits"] == 2
    assert back.attributes.get("dict_misses", 0) <= 1   # 0 if seen before


def test_dictionary_cache_is_bounded_least_recently_used_out(monkeypatch):
    serde._DICT_CACHE.clear()
    keep = (99, 99, 0)
    n_more = serde._DICT_CACHE_ENTRIES + 10

    def pull(key, n_words=2):
        words = ["a"] + [f"b{key[0]}.{i:04d}" for i in range(1, n_words)]
        blocks, n, _ = decode_serialized_page(
            _foreign_frame(words, [0, 1], key))
        return wire_blocks_to_page(blocks, [VARCHAR], n,
                                   compact_strings=False)

    kept = pull(keep).columns[0].dictionary
    for i in range(n_more):
        pull((i + 1000, 1, 0))
        if i % 8 == 0:
            pull(keep)                 # in use: stays
        assert len(serde._DICT_CACHE) <= serde._DICT_CACHE_ENTRIES
    assert len(serde._DICT_CACHE) == serde._DICT_CACHE_ENTRIES
    assert pull(keep).columns[0].dictionary is kept
    assert (1000, 1, 0) not in serde._DICT_CACHE

    # the bound on words held: the oldest go until the rest fit, and a
    # dictionary over the whole budget is decoded for its page, not kept
    serde._DICT_CACHE.clear()
    monkeypatch.setattr(serde, "_DICT_CACHE_WORDS", 100)
    for i in range(5):
        pull((i + 1, 2, 0), n_words=40)
    assert list(serde._DICT_CACHE) == [(4, 2, 0), (5, 2, 0)]
    page = pull((6, 2, 0), n_words=101)
    assert len(page.columns[0].dictionary) == 101
    assert list(serde._DICT_CACHE) == [(4, 2, 0), (5, 2, 0)]


def test_dictionary_cache_under_many_fetcher_threads():
    """The exchange's fetcher threads share the cache: more threads than
    cores, a short switch interval, more dictionaries than the bound, so
    hits, misses and evictions interleave. Every page still decodes to
    its own words, and the bound holds."""
    import sys
    import threading

    serde._DICT_CACHE.clear()
    n_dicts = serde._DICT_CACHE_ENTRIES + 16
    frames = {}
    for i in range(n_dicts):
        words = [f"d{i:03d}.w{j:02d}" for j in range(20)]
        frames[i] = (words, _foreign_frame(words, [19, 0, 7],
                                           (i + 1, 77, 0)))
    failures, over = [], []

    def fetch(seed):
        rng = np.random.default_rng(seed)
        for i in rng.integers(0, n_dicts, 80):
            words, data = frames[int(i)]
            blocks, n, _ = decode_serialized_page(data)
            page = wire_blocks_to_page(blocks, [VARCHAR], n,
                                       compact_strings=False)
            if page.to_pylist() != [(words[19],), (words[0],),
                                    (words[7],)]:
                failures.append(int(i))
            with serde._DICT_CACHE_LOCK:
                held = len(serde._DICT_CACHE)
            if held > serde._DICT_CACHE_ENTRIES:
                over.append(held)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=fetch, args=(s,))
                   for s in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(before)
    assert not any(t.is_alive() for t in threads)
    assert not failures and not over
    assert len(serde._DICT_CACHE) == serde._DICT_CACHE_ENTRIES
