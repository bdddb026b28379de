"""The miner's resident route over several chips: a job that sees several
local devices builds one mesh over them (`utils.devices.job_mesh`), every
device holds a contiguous run of the slabs of bit columns, the two support
programs run on each device's own words under `shard_map`, and one `psum`
a round adds the int32 counts. Held here, on the suite's 8 virtual CPU
devices with slabs cut to 4,096 baskets: 1, 2, 4 and 8 devices write the
same bytes; the shards' own counts add up to the one-device result; a
count past 2^24 survives the float32 blocks and the `psum`; the route is
taken by a device's share of the bytes; the spans say how many devices;
and a process that sees one device runs the one-device programs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from avenir_tpu import obs
from avenir_tpu.models.association import FrequentItemsApriori
from avenir_tpu.ops import bitset
from avenir_tpu.parallel import data_mesh
from avenir_tpu.runner import run_job
from avenir_tpu.utils import devices

from test_association_resident import CONF, basket_file, out_bytes

SLAB_BASKETS = bitset.SLAB_ALIGN * 32          # 4,096: the least slab
#: 13 slabs: a run of 13, 7, 4 or 2 a device, so that two devices hold 7
#: and 6 of them, four hold 4, 4, 4 and 1, and of eight the seventh holds
#: one and the eighth padding alone
BASKETS = 12 * SLAB_BASKETS + 847


def see(monkeypatch, count):
    """The process sees `count` local devices, as if the rest were hidden
    from the runtime; slabs are cut to the least size."""
    devs = jax.devices()[:count]
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devs)
    whole = bitset.slab_words_for
    monkeypatch.setattr(bitset, "slab_words_for",
                        lambda n, most_rows=SLAB_BASKETS: whole(n, most_rows))


@pytest.fixture(scope="module")
def baskets(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fia_mesh")
    path = str(tmp / "baskets.csv")
    basket_file(path, n=BASKETS, seed=5)
    assert BASKETS % 32 and BASKETS % (32 * 8)
    return {"tmp": tmp, "path": path}


def mined_on(baskets, monkeypatch, count):
    """The job run in a process that sees `count` devices: (spans, bytes)."""
    out = str(baskets["tmp"] / f"out_{count}")
    with monkeypatch.context() as mp:
        see(mp, count)
        with obs.capture() as rec:
            run_job("frequentItemsApriori", CONF, [baskets["path"]], out)
    return rec.spans(), out_bytes(out)


# ------------------------------------------------------- the bytes written
@pytest.mark.parametrize("count", [2, 4, 8])
def test_any_number_of_devices_writes_the_bytes_one_device_writes(
        baskets, monkeypatch, count):
    _spans, want = mined_on(baskets, monkeypatch, 1)
    spans, got = mined_on(baskets, monkeypatch, count)
    assert sorted(want) == ["itemsets-1.txt", "itemsets-2.txt",
                            "itemsets-3.txt"]
    assert all(len(v) > 100 for v in want.values())
    assert got == want
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    (root,) = by_name["fia.mine"]
    (put,) = by_name["fia.put"]
    assert root.attrs["resident"] is True and root.attrs["devices"] == count
    assert root.attrs["rows"] == BASKETS
    run = -(-13 // count)
    rows = bitset.column_rows(root.attrs["frequent"])
    assert put.attrs["slabs"] == 13 and put.attrs["devices"] == count
    assert put.attrs["nbytes"] == 13 * rows * 128 * 4
    assert put.attrs["nbytes_per_device"] == run * rows * 128 * 4
    # a slab a fold, padding slabs are not sent
    assert len(by_name["stream.fold"]) == 13
    assert [s.attrs["devices"] for s in by_name["fia.round.dispatch"]] == [
        count, count]
    assert devices.device_report()["devices_used"] >= count


def test_one_visible_device_runs_the_one_device_programs(baskets, monkeypatch):
    """No mesh is built, the spans are what they were, and neither sharded
    program is traced or compiled."""
    with monkeypatch.context() as mp:
        see(mp, 1)
        assert devices.job_mesh() is None
    sizes = (bitset._pair_gram_mesh._cache_size(),
             bitset._set_supports_mesh._cache_size())
    spans, _got = mined_on(baskets, monkeypatch, 1)
    assert sizes == (bitset._pair_gram_mesh._cache_size(),
                     bitset._set_supports_mesh._cache_size())
    (root,) = [s for s in spans if s.name == "fia.mine"]
    (put,) = [s for s in spans if s.name == "fia.put"]
    assert root.attrs["devices"] == 1 and root.attrs["resident"] is True
    assert set(put.attrs) - set(obs.USAGE_ATTRS) == {"nbytes", "slabs"}
    assert [s.attrs["devices"] for s in spans
            if s.name == "fia.round.dispatch"] == [1, 1]


def test_the_jobs_mesh_is_one_data_axis_over_the_local_devices(monkeypatch):
    mesh = devices.job_mesh()
    assert mesh.axis_names == ("data",) and mesh.size == len(jax.devices())
    assert list(mesh.devices.flat) == jax.local_devices()
    see(monkeypatch, 4)
    four = devices.job_mesh()
    assert list(four.devices.flat) == jax.devices()[:4]
    assert four == devices.job_mesh()       # equal meshes: one compilation


# -------------------------------------------- the parts add up to the whole
@pytest.fixture(scope="module")
def placed():
    """Six random slabs over 64 item rows on four devices (runs of two,
    the fourth device holds padding alone) and on one."""
    rng = np.random.default_rng(8)
    slabs = rng.integers(0, 1 << 32, (6, 64, 128), dtype=np.uint64).astype(
        np.uint32)
    slabs &= rng.integers(0, 1 << 32, slabs.shape, dtype=np.uint64).astype(
        np.uint32)                           # a quarter of the bits
    mesh = data_mesh(jax.devices()[:4])
    with obs.capture() as rec:
        cols = FrequentItemsApriori._put_resident(slabs, mesh)
    (put,) = [s for s in rec.spans() if s.name == "fia.put"]
    one = FrequentItemsApriori._put_resident(slabs)
    cands = np.array([[0, 1, 2], [5, 9, 44], [7, 7, 7], [63, 1, 30]], np.int32)
    return {"slabs": slabs, "mesh": mesh, "cols": cols, "one": one,
            "put": put, "cands": cands}


def test_every_device_holds_its_run_of_the_slabs_and_no_other(placed):
    slabs, cols = placed["slabs"], placed["cols"]
    assert cols.shape == (64, 4 * 2 * 128)
    assert cols.sharding.spec == jax.sharding.PartitionSpec(None, "data")
    shards = sorted(cols.addressable_shards, key=lambda s: s.index[1].start)
    assert [s.device for s in shards] == jax.devices()[:4]
    for d, shard in enumerate(shards):
        held = np.asarray(shard.data)
        assert held.shape == (64, 256)
        want = [slabs[at] if at < 6 else np.zeros((64, 128), np.uint32)
                for at in (2 * d, 2 * d + 1)]
        np.testing.assert_array_equal(held, np.concatenate(want, axis=1))
    assert {k: v for k, v in placed["put"].attrs.items()
            if k not in obs.USAGE_ATTRS} == {
        "nbytes": slabs.nbytes, "slabs": 6, "devices": 4,
        "nbytes_per_device": 64 * 256 * 4}
    # the real slabs side by side are what one device holds
    np.testing.assert_array_equal(np.asarray(placed["one"]),
                                  np.asarray(cols)[:, :6 * 128])


@pytest.mark.parametrize("program", ["pairs", "sets"])
def test_the_shards_own_counts_add_up_to_the_one_device_result(placed,
                                                               program):
    cols, mesh, cands = placed["cols"], placed["mesh"], placed["cands"]
    if program == "pairs":
        whole = np.asarray(bitset._pair_gram(placed["one"], 128))
        parts = [np.asarray(bitset._pair_gram(s.data, 128))
                 for s in cols.addressable_shards]
        summed = bitset._pair_gram_mesh(cols, mesh, 128)
        bits = np.unpackbits(np.asarray(placed["one"]).view(np.uint8), axis=1)
        np.testing.assert_array_equal(
            whole, bits.astype(np.int64) @ bits.astype(np.int64).T)
    else:
        whole = np.asarray(bitset._set_supports(placed["one"],
                                                jnp.asarray(cands)))
        parts = [np.asarray(bitset._set_supports(s.data, jnp.asarray(cands)))
                 for s in cols.addressable_shards]
        summed = bitset._set_supports_mesh(cols, cands, mesh)
    assert len(parts) == 4 and whole.any()
    assert not parts[3].any()                # padding adds to no count
    assert all(p.any() for p in parts[:3])
    np.testing.assert_array_equal(sum(parts), whole)
    np.testing.assert_array_equal(np.asarray(summed), whole)
    assert summed.dtype == jnp.int32 and summed.sharding.is_fully_replicated


@pytest.mark.parametrize("program", ["pairs", "sets"])
def test_a_count_past_two_to_the_24th_survives_the_blocks_and_the_psum(
        program):
    """One pair in every one of 2^24 + 1 baskets, and a third item in all
    of them but three, over four devices: no device counts past 2^22, a
    float32 sum of the devices' counts would stop at 16,777,216."""
    n = (1 << 24) + 1
    words = -(-n // (32 * 4 * 4096)) * 4 * 4096
    cols = np.zeros((32, words), np.uint32)
    cols[:3, :n // 32] = 0xFFFFFFFF
    cols[:3, n // 32] = (1 << (n % 32)) - 1
    cols[2, [5, words // 4 + 5, 3 * (words // 4) + 5]] &= ~np.uint32(1)
    assert np.float32(1 << 24) + np.float32(1) == np.float32(1 << 24)
    mesh = data_mesh(jax.devices()[:4])
    slabs = np.ascontiguousarray(
        cols.reshape(32, words // 4096, 4096).transpose(1, 0, 2))
    cols_d = FrequentItemsApriori._put_resident(slabs, mesh)
    if program == "pairs":
        gram = np.asarray(bitset._pair_gram_mesh(cols_d, mesh, 4096))
        assert gram[0, 1] == n == gram[1, 1] and gram[0, 2] == n - 3
    else:
        got = np.asarray(bitset._set_supports_mesh(
            cols_d, np.array([[0, 1], [0, 2]], np.int32), mesh))
        assert got.tolist() == [n, n - 3]


# ---------------------------------------------------------------- the rule
def test_four_devices_take_on_what_one_cannot_hold(monkeypatch):
    """The cell `fia-t10i4-mesh4.remine`: 167,772,160 baskets over 857
    frequent items are 18.1 GB of columns, over 0.6 of a 16 GiB device;
    a quarter of them is 4.53 GB."""
    monkeypatch.setattr(FrequentItemsApriori, "device_bytes_limit",
                        staticmethod(lambda: 16 << 30))
    miner = FrequentItemsApriori(0.0033)
    n = 4 * 40 << 20
    assert miner.resident_words(n, 857) is None
    assert miner.resident_words(n, 857, 2) == (4096, 640)    # 9.06 GB each
    assert miner.resident_words(n, 857, 4) == (4096, 320)
    assert 320 * 4096 * 27 * 32 * 4 == 4_529_848_320 == n // 4 * 108
    # one chip's most: some 95M baskets at 27 words
    assert miner.resident_words(91 << 20, 857) is not None
    assert miner.resident_words(92 << 20, 857) is None
    # runs are of equal length: five slabs on four devices are runs of two
    assert miner.resident_words(5 * (1 << 17), 857, 4) == (4096, 2)
    assert miner.resident_words(1, 3, 8) == (128, 1)


def test_the_limit_is_the_least_over_the_local_devices(monkeypatch):
    class Chip:
        def __init__(self, limit):
            self.limit = limit

        def memory_stats(self):
            return {"bytes_limit": self.limit} if self.limit else None

    chips = [Chip(16 << 30), Chip(12 << 30), Chip(0), Chip(16 << 30)]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)
    assert FrequentItemsApriori.device_bytes_limit() == 2 << 30
    monkeypatch.setattr(jax, "local_devices", lambda: chips[:2])
    assert FrequentItemsApriori.device_bytes_limit() == 12 << 30


def test_a_job_too_large_for_every_share_still_mines_the_stream(
        baskets, monkeypatch, tmp_path):
    _spans, want = mined_on(baskets, monkeypatch, 1)
    see(monkeypatch, 4)
    monkeypatch.setattr(FrequentItemsApriori, "device_bytes_limit",
                        staticmethod(lambda: 1 << 16))
    with obs.capture() as rec:
        run_job("frequentItemsApriori", CONF, [baskets["path"]],
                str(tmp_path / "out"))
    roots = [s for s in rec.spans() if s.name == "fia.mine"]
    assert [r.attrs["resident"] for r in roots] == [False, False]
    assert roots[0].attrs["devices"] == 4
    assert "fia.put" not in {s.name for s in rec.spans()}
    assert out_bytes(str(tmp_path / "out")) == want
