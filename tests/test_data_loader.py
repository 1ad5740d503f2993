"""Native C++ IO layer tests: record round-trip, shuffled epochs, prefetch."""
import numpy as np
import pytest

from autodist_tpu.data.loader import BatchLoader, RecordDataset, write_records


@pytest.fixture
def dataset(tmp_path):
    data = np.arange(100 * 4, dtype=np.float32).reshape(100, 4)
    path = str(tmp_path / "records.bin")
    write_records(path, data)
    ds = RecordDataset(path, (4,), np.float32)
    yield ds, data
    ds.close()


def test_native_lib_built(dataset):
    ds, _ = dataset
    assert ds._ds, "native loader should be available in this image"


def test_len_and_read_batch(dataset):
    ds, data = dataset
    assert len(ds) == 100
    got = ds.read_batch([0, 99, 50])
    np.testing.assert_array_equal(got, data[[0, 99, 50]])


def test_read_batch_out_of_range(dataset):
    ds, _ = dataset
    with pytest.raises(IndexError):
        ds.read_batch([100])


def test_batch_loader_covers_epoch(dataset):
    ds, data = dataset
    ld = BatchLoader(ds, batch_size=10, shuffle=True, seed=1, threads=2)
    seen = set()
    for _ in range(10):  # one epoch worth
        b = next(ld)
        assert b.shape == (10, 4)
        seen.update(int(r[0] // 4) for r in b)  # first element encodes row
    ld.close()
    # shuffled epoch permutation must cover (nearly) all rows
    assert len(seen) > 90


def test_batch_loader_deterministic_records(dataset):
    ds, data = dataset
    ld = BatchLoader(ds, batch_size=8, shuffle=False, seed=0, threads=1)
    b = next(ld)
    ld.close()
    # every returned record must be a real dataset row
    rows = {tuple(r) for r in data}
    for r in b:
        assert tuple(r) in rows


def test_sharded_loaders_partition_dataset(dataset):
    """Multi-host feed split: K sharded loaders jointly cover the dataset
    exactly once per epoch, with disjoint shards (native path)."""
    ds, data = dataset
    K = 4
    seen = [set() for _ in range(K)]
    for k in range(K):
        ld = BatchLoader(ds, batch_size=5, shuffle=True, seed=7,
                         threads=2, shard_index=k, shard_count=K)
        for _ in range(5):  # 25 records = one shard epoch
            for r in next(ld):
                seen[k].add(int(r[0] // 4))
        ld.close()
    for a in range(K):
        assert seen[a] == set(range(a, 100, K))  # exactly its residue class


def test_sharded_loader_python_fallback(tmp_path, monkeypatch):
    """The numpy fallback (no native lib) shards identically."""
    import autodist_tpu.data.loader as L

    monkeypatch.setattr(L, "_lib", False)  # pretend no compiler/native lib
    data = np.arange(20 * 2, dtype=np.float32).reshape(20, 2)
    path = str(tmp_path / "r2.bin")
    write_records(path, data)
    ds = RecordDataset(path, (2,), np.float32)
    assert ds._ds is None  # memmap fallback active
    ld = BatchLoader(ds, batch_size=5, shuffle=True, seed=3,
                     shard_index=1, shard_count=2)
    seen = set()
    for _ in range(2):  # one shard epoch (10 records)
        seen.update(int(r[0] // 2) for r in next(ld))
    assert seen == set(range(1, 20, 2))
    ld.close()
    ds.close()


def test_bad_shard_args(dataset):
    ds, _ = dataset
    with pytest.raises(ValueError):
        BatchLoader(ds, 4, shard_index=3, shard_count=2)


def test_device_prefetcher_preserves_order_and_values(dataset):
    import jax.numpy as jnp
    import optax

    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.data.loader import DevicePrefetcher
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce

    ds, data = dataset
    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(8),
                  strategy_builder=AllReduce())
    sess = ad.distribute(lambda p, b: jnp.mean((b @ p["w"]) ** 2),
                         {"w": jnp.ones((4,))}, optax.sgd(0.1))
    host_batches = [data[i * 8:(i + 1) * 8] for i in range(4)]
    pf = DevicePrefetcher(iter(host_batches), sess, depth=2)
    got = [np.asarray(b) for b in pf]
    assert len(got) == 4
    for h, g in zip(host_batches, got):
        np.testing.assert_array_equal(h, g)
    # prefetched batches run through the session directly
    m = sess.run(sess._shard_batch(host_batches[0]))
    assert np.isfinite(float(m["loss"]))


@pytest.mark.parametrize("stale", ["missing", "older_than_source"])
def test_native_lib_rebuilt_from_source_when_stale(monkeypatch, stale):
    """The .so is untracked: what runs is built from native/autodist_io.cpp
    whenever the library is missing or older than that source."""
    import os

    import autodist_tpu.data.loader as L

    assert L._load_native()              # make sure one is on disk
    if stale == "missing":
        os.unlink(L._SO_PATH)
    else:
        old = os.path.getmtime(L._SRC_PATH) - 10
        os.utime(L._SO_PATH, (old, old))
    monkeypatch.setattr(L, "_lib", None)
    monkeypatch.setattr(L, "_built_here", False)
    assert L._load_native()
    assert os.path.getmtime(L._SO_PATH) >= os.path.getmtime(L._SRC_PATH)
    assert L.loader_kind() == "native (built in this process)"


def test_loader_kind_names_the_numpy_fallback(monkeypatch):
    import autodist_tpu.data.loader as L

    monkeypatch.setattr(L, "_lib", False)
    assert L.loader_kind() == "numpy"
