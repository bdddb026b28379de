"""The miner's resident route: the file read whole, tokenised by two
native passes (`native.ingest.basket_scan_native`, `basket_pack_native`),
the baskets packed as bit columns (`ops.bitset`) and kept on the device
across the rounds, pairs counted as one Gram matrix and longer sets by the
popcount of ANDed columns. Held here: the three ways to mine a file write
the same bytes; the native scan gives the same vocabulary, counts and
columns at any thread count and as a Python transcription of it; counts
stay exact past 2^24 baskets; the route is taken by rules the code can
observe; the spans; and, compiled for a described v5e at the benchmark
cell's size, a basket costs words x 4 bytes and no padding."""

import os
from itertools import combinations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from avenir_tpu import obs
from avenir_tpu.models.association import (FrequentItemsApriori, ItemSetList,
                                           TransactionSet)
from avenir_tpu.native import ingest
from avenir_tpu.ops import bitset
from avenir_tpu.runner import run_job

CONF = {"fia.support.threshold": "0.02", "fia.item.set.length": "3",
        "fia.skip.field.count": "1"}
LEAVES = ("fia.read", "fia.scan", "fia.put", "fia.round.candidates",
          "fia.round.dispatch", "fia.round.fetch", "fia.output.write")


def basket_file(path, n=3000, items=70, seed=3):
    """Baskets over `items` items with a few planted sets, every fifth
    row naming one of its items twice; returns the rows' item lists."""
    rng = np.random.default_rng(seed)
    planted = [rng.choice(items, size=rng.integers(2, 5), replace=False)
               for _ in range(12)]
    rows = []
    with open(path, "w") as fh:
        for t in range(n):
            got = set(rng.choice(items, size=rng.integers(1, 6)).tolist())
            for p in rng.choice(len(planted), size=2):
                if rng.random() < 0.6:
                    got.update(planted[p].tolist())
            row = [f"i{v:02d}" for v in rng.permutation(sorted(got))]
            if t % 5 == 0:
                row.append(row[0])           # an item twice counts once
            rows.append(row)
            fh.write(",".join([f"T{t:06d}"] + row) + "\n")
    return rows


def out_bytes(folder):
    return {name: open(os.path.join(folder, name), "rb").read()
            for name in sorted(os.listdir(folder))}


def brute_force(rows, threshold, longest):
    sets = [set(r) for r in rows]
    names = sorted({v for s in sets for v in s})
    out = {}
    for k in range(1, longest + 1):
        for cand in combinations(names, k):
            count = sum(1 for s in sets if s.issuperset(cand))
            if count > threshold * len(sets):
                out[cand] = count
    return out


def spans_of(job, conf, path, out, patch=None):
    mp = pytest.MonkeyPatch()
    for name, value in (patch or {}).items():
        mp.setattr(FrequentItemsApriori, name, staticmethod(lambda v=value: v))
    try:
        with obs.capture() as rec:
            run_job(job, conf, [path], out)
    finally:
        mp.undo()
    return rec.spans()


@pytest.fixture(scope="module")
def mined(tmp_path_factory):
    """One file mined three ways: the job as it runs (resident), the job
    with a device too small to hold the baskets (re-scan), and `mine()`
    over the rows in memory."""
    tmp = tmp_path_factory.mktemp("fia")
    path = str(tmp / "baskets.csv")
    rows = basket_file(path)
    job = "frequentItemsApriori"
    spans = spans_of(job, CONF, path, str(tmp / "resident"))
    rescan = spans_of(job, CONF, path, str(tmp / "rescan"),
                      {"device_bytes_limit": 0})
    os.makedirs(tmp / "memory")
    levels = FrequentItemsApriori(0.02, max_length=3).mine(
        TransactionSet.from_csv(path))
    for k, isl in enumerate(levels, start=1):
        isl.save(str(tmp / "memory" / f"itemsets-{k}.txt"))
    return {"tmp": tmp, "rows": rows, "spans": spans, "rescan_spans": rescan,
            "path": path}


@pytest.mark.parametrize("other", ["rescan", "memory"])
def test_the_routes_write_the_same_bytes(mined, other):
    want = out_bytes(mined["tmp"] / "resident")
    assert sorted(want) == ["itemsets-1.txt", "itemsets-2.txt",
                            "itemsets-3.txt"]
    assert out_bytes(mined["tmp"] / other) == want


def test_the_sets_are_the_brute_force_ones(mined):
    want = brute_force(mined["rows"], 0.02, 3)
    n = len(mined["rows"])
    got = {}
    for k in (1, 2, 3):
        isl = ItemSetList.load(
            str(mined["tmp"] / "resident" / f"itemsets-{k}.txt"), k)
        got.update({s.items: s.support for s in isl.item_sets})
    assert set(got) == set(want) and len(want) > 150
    assert max(len(s) for s in want) == 3
    for items, count in want.items():
        assert f"{got[items]:.6f}" == f"{count / n:.6f}"


def test_the_resident_route_names_its_phases(mined):
    by_name = {}
    for s in mined["spans"]:
        by_name.setdefault(s.name, []).append(s)
    for leaf in LEAVES:
        assert leaf in by_name, leaf
    (root,) = by_name["fia.mine"]
    assert root.attrs["resident"] is True and root.attrs["rows"] == 3000
    assert root.attrs["vocab"] == 70 and root.attrs["rounds"] == 3
    assert root.attrs["words"] == -(-root.attrs["frequent"] // 32)
    # one read, the two native passes, one put; neither a block's parse
    # nor a block's cache on this route
    assert len(by_name["fia.read"]) == 1 == len(by_name["fia.put"])
    assert [s.attrs["nth"] for s in by_name["fia.scan"]] == [1, 2]
    tokens = sum(len(r) for r in mined["rows"])
    for s in by_name["fia.scan"]:
        assert s.attrs["tokens"] == tokens and s.attrs["threads"] == 1
    assert by_name["fia.read"][0].attrs["nbytes"] == os.path.getsize(
        mined["path"])
    assert "stream.parse" not in by_name and "stream.replay" not in by_name
    assert {s.attrs["sink"] for s in by_name["stream.fold"]} == {
        "apriori_resident"}
    assert [s.attrs["k"] for s in by_name["fia.round.fetch"]] == [2, 3]
    pairs, triples = by_name["fia.round.fetch"]
    f = root.attrs["frequent"]
    assert pairs.attrs["candidates"] == f * (f - 1) // 2
    assert 0 < triples.attrs["kept"] <= triples.attrs["candidates"]
    # every leaf lies inside fia.mine or, the write, after it; all are the
    # job's thread's own
    for s in mined["spans"]:
        if s.name in LEAVES:
            assert s.tid == root.tid
            if s.name != "fia.output.write":
                assert root.t0 <= s.t0 and s.t0 + s.dur <= root.t0 + root.dur


def test_the_rescan_route_says_so_and_folds_in_spans(mined):
    names = [s.name for s in mined["rescan_spans"]]
    roots = [s for s in mined["rescan_spans"] if s.name == "fia.mine"]
    # pass 1 of the whole file found that the baskets do not fit; then the
    # stream was mined
    assert [r.attrs["resident"] for r in roots] == [False, False]
    assert roots[1].attrs["rounds"] == 3 and "rounds" not in roots[0].attrs
    assert "fia.put" not in names and "stream.fold" in names
    assert {s.attrs["sink"] for s in mined["rescan_spans"]
            if s.name == "stream.fold"} == {"apriori_support"}


@pytest.mark.parametrize("why, conf, patch", [
    ("a stated block size asks for the block scan",
     {"fia.stream.block.size.mb": "0.01"}, {}),
    ("exact transaction ids", {"fia.emit.trans.id": "true"}, {}),
    ("a file over the host's share", {}, {"host_bytes": 1000}),
    ("a delimiter the native parser does not split on",
     {"field.delim.regex": ";;", "field.delim.out": ";;"}, {})])
def test_the_stream_is_mined_where_the_whole_file_cannot_be(
        mined, tmp_path, why, conf, patch):
    path = mined["path"]
    if "field.delim.regex" in conf:
        path = str(tmp_path / "other.csv")
        with open(mined["path"]) as src, open(path, "w") as dst:
            dst.write(src.read().replace(",", ";;"))
    spans = spans_of("frequentItemsApriori", {**CONF, **conf}, path,
                     str(tmp_path / "out"), patch)
    names = {s.name for s in spans}
    assert "fia.read" not in names and "fia.put" not in names, why
    (root,) = [s for s in spans if s.name == "fia.mine"]
    assert root.attrs["resident"] is False
    if not conf.get("fia.emit.trans.id") and "field.delim.regex" not in conf:
        assert out_bytes(tmp_path / "out") == out_bytes(
            mined["tmp"] / "resident")


def test_several_files_are_one_buffer(mined, tmp_path):
    """Two inputs, the first ending inside a line: mined as one file."""
    with open(mined["path"]) as fh:
        lines = fh.readlines()
    a, b = str(tmp_path / "a.csv"), str(tmp_path / "b.csv")
    with open(a, "w") as fh:
        fh.write("".join(lines[:1700]).rstrip("\n"))
    with open(b, "w") as fh:
        fh.writelines(lines[1700:])
    with obs.capture() as rec:
        run_job("frequentItemsApriori", CONF, [a, b], str(tmp_path / "out"))
    (root,) = [s for s in rec.spans() if s.name == "fia.mine"]
    assert root.attrs["resident"] is True and root.attrs["rows"] == 3000
    assert out_bytes(tmp_path / "out") == out_bytes(mined["tmp"] / "resident")


# ------------------------------------------------------- the native scan
EDGE_ROWS = [
    b"T1,a,b,a",                 # a token twice
    b"",                         # no row
    b"T2,b,,c \r",               # an empty token, trailing CR and space
    b" \t\r",                    # whitespace alone: no row
    b"T3",                       # a row of no items
    b"T4,*,a_token_longer_than_eight_bytes,\ta ",
    b"T5,*",                     # the marker alone
    b"T6,eightbyt,eightbyt,ninebytes",
    b",,",                       # delimiters alone: a row of no items
]


def transcription(data, skip, marker):
    """What the two native passes compute, a line and a token at a time:
    (vocabulary in order of first appearance, baskets an item, the set of
    item codes of every row)."""
    vocab, counts, rows = {}, [], []
    for ln in data.split(b"\n"):
        if not ln.strip(b" \t\r"):
            continue
        held = set()
        for tok in ln.split(b",")[skip:]:
            tok = tok.strip(b" \t\r")
            if not tok or tok == marker:
                continue
            if tok not in vocab:
                vocab[tok] = len(vocab)
                counts.append(0)
            if vocab[tok] not in held:
                held.add(vocab[tok])
                counts[vocab[tok]] += 1
        rows.append(held)
    return [t.decode() for t in vocab], counts, rows


def columns_of_rows(rows, item_row, v_rows, slab_words):
    """uint32 [slabs, v_rows, slab_words] of rows given as code sets."""
    n_slabs = max(-(-len(rows) // (slab_words * 32)), 1)
    cols = np.zeros((n_slabs, v_rows, slab_words), np.uint32)
    for t, held in enumerate(rows):
        w = t // 32
        for code in held:
            if item_row[code] >= 0:
                cols[w // slab_words, item_row[code], w % slab_words] |= \
                    np.uint32(1 << (t % 32))
    return cols


@pytest.mark.parametrize("last_newline", [True, False])
def test_the_native_scan_on_the_rows_that_are_hard(last_newline):
    data = b"\n".join(EDGE_ROWS * 9) + (b"\n" if last_newline else b"")
    vocab, counts, rows = transcription(data, 1, b"*")
    assert vocab == ["a", "b", "c", "a_token_longer_than_eight_bytes",
                     "eightbyt", "ninebytes"]
    assert len(rows) == 7 * 9 and counts[0] == 18
    scan = ingest.basket_scan_native(data, ",", 1, "*")
    assert (scan.vocab, scan.counts.tolist(), scan.rows) == (
        vocab, counts, len(rows))
    assert scan.tokens == 10 * 9 and scan.threads == 1
    assert scan.blob == b"".join(t.encode() + b"\n" for t in vocab)
    item_row = np.array([0, -1, 2, 1, 3, 4], np.int32)     # b is not kept
    got = ingest.basket_pack_native(data, ",", 1, "*", scan, item_row, 32, 128)
    want = columns_of_rows(rows, item_row, 32, 128)
    assert got.shape == (1, 32, 128) and got.dtype == np.uint32
    np.testing.assert_array_equal(got, want)
    assert int(np.bitwise_count(got).sum()) == sum(
        1 for held in rows for c in held if item_row[c] >= 0)
    # without a marker the star is an item like another; skip 2 drops a
    # field more
    assert "*" in ingest.basket_scan_native(data, ",", 1).vocab
    assert ingest.basket_scan_native(data, ",", 2, "*").vocab[0] == "b"


@pytest.fixture(scope="module")
def striped():
    """56 MB of baskets, so that 13 stripes of 4 MB are cut: items that
    first appear ever later in the file (a stripe finds some that no
    stripe before it has), the hard rows strewn among them, and the last
    line without its newline; with the transcription's answer."""
    rng = np.random.default_rng(11)
    n = 1_800_000
    pool = [f"it{v:04d}".encode() for v in range(600)] + [
        f"a_long_item_name_{v:03d}".encode() for v in range(40)]
    sizes = rng.integers(1, 7, n).tolist()
    picks = rng.random((n, 6))
    top = 40 + (600 * np.arange(n)) // n             # the vocabulary grows
    picks = (picks * top[:, None]).astype(np.int64).tolist()
    rows = []
    for t in range(n):
        if t % 50_000 == 49_999:
            rows.extend(EDGE_ROWS)
        rows.append(b"%09d," % t + b",".join(
            [pool[i] for i in picks[t][:sizes[t]]]))
    data = b"\n".join(rows)
    assert len(data) > 13 * (4 << 20) and not data.endswith(b"\n")
    vocab, counts, held = transcription(data, 1, b"*")
    return {"data": data, "vocab": vocab, "counts": counts, "rows": held,
            "bytes": {}}


@pytest.mark.parametrize("threads", [1, 2, 4, 13])
def test_the_native_scan_is_the_same_at_any_thread_count(striped, threads):
    data, vocab, counts, rows = (striped[k] for k in (
        "data", "vocab", "counts", "rows"))
    scan = ingest.basket_scan_native(data, ",", 1, "*", threads=threads)
    assert scan.threads == threads
    assert scan.vocab == vocab and scan.rows == len(rows)
    assert scan.counts.tolist() == counts
    keep = np.flatnonzero(scan.counts > 0.002 * scan.rows)
    assert 100 < len(keep) < len(vocab)
    item_row = np.full(len(vocab), -1, np.int32)
    item_row[keep] = np.arange(len(keep), dtype=np.int32)
    v_rows, slab = bitset.column_rows(len(keep)), 1024
    got = ingest.basket_pack_native(data, ",", 1, "*", scan, item_row, v_rows,
                                    slab, threads=threads)
    assert got.shape == (-(-len(rows) // (slab * 32)), v_rows, slab)
    # every item's popcount is its count, and a sample of the baskets (the
    # stripes' edges are somewhere among them) holds the rows' own bits
    np.testing.assert_array_equal(
        np.bitwise_count(got).sum(axis=(0, 2), dtype=np.int64)[:len(keep)],
        scan.counts[keep])
    at = np.unique(np.concatenate([
        np.arange(0, len(rows), 997), np.arange(len(rows) - 70, len(rows))]))
    for t in at.tolist():
        w = t // 32
        bits = got[w // slab, :, w % slab] >> np.uint32(t % 32) & 1
        assert {i for i, bit in enumerate(bits.tolist()) if bit} == {
            int(item_row[c]) for c in rows[t] if item_row[c] >= 0}
    # and the same bytes as whichever thread count packed first
    assert striped["bytes"].setdefault("cols", got.tobytes()) == got.tobytes()


@pytest.mark.parametrize("threads", [1, 13])
def test_files_are_read_whole_by_stripes_into_one_buffer(striped, tmp_path,
                                                         threads):
    """`read_files_native`: every stripe preads its own byte range; the
    buffer is the files' bytes one after the other, a file that ends
    inside a line ended there."""
    data = striped["data"]
    a, b, c = (str(tmp_path / name) for name in ("a.csv", "b.csv", "c.csv"))
    cut = data.index(b"\n", len(data) // 3) + 1
    with open(a, "wb") as fh:
        fh.write(data[:cut])
    with open(b, "wb") as fh:
        fh.write(b"")
    with open(c, "wb") as fh:
        fh.write(data[cut:])                 # no newline at its end
    got = ingest.read_files_native([a, b, c], threads=threads)
    assert got.dtype == np.uint8 and got.tobytes() == data + b"\n"
    scan = ingest.basket_scan_native(got, ",", 1, "*", threads=threads)
    assert scan.vocab == striped["vocab"] and scan.rows == len(striped["rows"])
    with pytest.raises(OSError):
        ingest.read_files_native([a, str(tmp_path / "none.csv")])


def test_a_file_of_another_row_count_is_not_packed(striped):
    data = striped["data"]
    scan = ingest.basket_scan_native(data[:1 << 16], ",", 1, "*")
    with pytest.raises(RuntimeError, match="row mismatch"):
        ingest.basket_pack_native(
            data[:1 << 15], ",", 1, "*", scan,
            np.zeros(len(scan.vocab), np.int32), 32, 128)


# ------------------------------------------------------------ the programs
def test_pairs_and_sets_against_the_multihot_rows(rng):
    mh = (rng.random((5000, 45)) < 0.3).astype(np.uint8)
    words = bitset.slab_words_for(5000)
    cols = jnp.asarray(bitset.columns_from_multihot(mh, words))
    gram = np.asarray(bitset._pair_gram(cols, words))
    np.testing.assert_array_equal(
        gram[:45, :45], mh.astype(np.int64).T @ mh.astype(np.int64))
    assert not gram[45:].any() and gram.dtype == np.int32
    cands = np.array([[0, 1, 2], [5, 9, 44], [7, 7, 7], [0, 0, 0]], np.int32)
    got = np.asarray(bitset._set_supports(cols, jnp.asarray(cands)))
    want = [int(mh[:, c].all(axis=1).sum()) for c in cands]
    assert got.tolist() == want and got.dtype == np.int32


def test_slabs_placed_are_the_columns(rng):
    """`_put_resident` over slab-major host columns: the device array is
    the slabs side by side."""
    slabs = rng.integers(0, 1 << 32, (3, 32, 128), dtype=np.uint64).astype(
        np.uint32)
    with obs.capture() as rec:
        cols = np.asarray(FrequentItemsApriori._put_resident(slabs))
    np.testing.assert_array_equal(cols, np.concatenate(list(slabs), axis=1))
    (put,) = [s for s in rec.spans() if s.name == "fia.put"]
    assert {k: v for k, v in put.attrs.items()
            if k not in obs.USAGE_ATTRS} == {"nbytes": slabs.nbytes, "slabs": 3}


@pytest.mark.parametrize("program", ["pairs", "sets"])
def test_a_count_stays_exact_past_two_to_the_24th(program):
    """One item in every one of 2^24 + 4,097 baskets and another in all
    but three: a float32 counter stops at 16,777,216."""
    n = (1 << 24) + 4097
    words = -(-n // (32 * 4096)) * 4096
    cols = np.zeros((32, words), np.uint32)
    cols[0, :n // 32] = 0xFFFFFFFF
    cols[0, n // 32] = (1 << (n % 32)) - 1
    cols[1] = cols[0]
    cols[1, 5] &= ~np.uint32(0b111)
    assert np.float32(1 << 24) + np.float32(1) == np.float32(1 << 24)
    if program == "pairs":
        gram = np.asarray(bitset._pair_gram(jnp.asarray(cols), 4096))
        assert gram[0, 0] == n and gram[0, 1] == n - 3 == gram[1, 1]
    else:
        got = np.asarray(bitset._set_supports(
            jnp.asarray(cols), jnp.asarray([[0, 0], [0, 1]], jnp.int32)))
        assert got.tolist() == [n, n - 3]


# ---------------------------------------------------------------- the rule
@pytest.mark.parametrize("limit, n, frequent, resident", [
    (16 << 30, 50_331_648, 857, True),       # the cell: 5.4 of 9.6 GiB
    (16 << 30, 100_663_296, 881, False),     # twice the baskets: 11.3 GB
    (16 << 30, 50_331_648, 2_000, False),    # 63 words a basket
    (2 << 30, 12_288, 870, True),
    (0, 12_288, 870, False)])
def test_the_route_is_taken_by_the_bytes_against_the_limit(
        monkeypatch, limit, n, frequent, resident):
    monkeypatch.setattr(FrequentItemsApriori, "device_bytes_limit",
                        staticmethod(lambda: limit))
    got = FrequentItemsApriori(0.0033).resident_words(n, frequent)
    assert (got is not None) == resident
    if resident:
        slab, slabs = got
        assert slab % bitset.SLAB_ALIGN == 0 and slab * 32 <= 1 << 17
        assert slab * slabs * 32 >= n > slab * (slabs - 1) * 32


def test_a_backend_that_states_no_limit_is_held_to_two_gib():
    assert jax.devices()[0].platform == "cpu"
    assert FrequentItemsApriori.device_bytes_limit() == 2 << 30
    assert FrequentItemsApriori.host_bytes() > 1 << 30


# ---------------------------------------- compiled for a described v5e
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("program", ["pairs", "sets", "place"])
def test_a_basket_costs_words_times_four_bytes_on_a_v5e(one_chip,
                                                        no_compile_cache,
                                                        program):
    """The benchmark cell's size: 50,331,648 baskets over 857 frequent
    items, 27 words. The resident columns are an argument of exactly
    n x 27 x 4 bytes: neither axis is padded to a tile."""
    n, frequent = 50_331_648, 857
    slab, slabs = FrequentItemsApriori(0.0033).resident_words(n, frequent) \
        or (4096, n // 32 // 4096)
    v_rows, words = bitset.column_rows(frequent), slab * slabs
    assert (v_rows, words, slab) == (27 * 32, n // 32, 4096)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cols = shape((v_rows, words), jnp.uint32)
    if program == "pairs":
        compiled = bitset._pair_gram.lower(
            cols, block_words=bitset.GRAM_BLOCK_WORDS).compile()
    elif program == "sets":
        compiled = bitset._set_supports.lower(
            cols, shape((4096, 3), jnp.int32)).compile()
    else:
        compiled = bitset.place_columns.lower(
            cols, shape((v_rows, slab), jnp.uint32),
            shape((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    resident = n * 27 * 4
    assert resident == 5_435_817_984
    # beside the columns: the candidates, or the slab and where it goes
    extra = v_rows * slab * 4 if program == "place" else 0
    assert 0 <= mem.argument_size_in_bytes - resident - extra <= 1 << 20
    assert mem.temp_size_in_bytes < 64 << 20
    if program == "place":                   # built in place: donated
        assert mem.alias_size_in_bytes == resident
