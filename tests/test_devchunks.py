"""Entry chunks gathered on the device (``repro.core.devchunks``).

The device-gathered group slab must equal the host gather
(``engine_order(...).gather(...).chunks[k]``) bit for bit, on a fresh index, a
transiently committed one, the same index rolled back, and where the
chunk width leaves ``-1`` pads; a served stream must decide the same on
either path; and a stream of transient commits must not compile the
device gather again.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import CopyConfig, build_index, devchunks
from repro.core.index import commit_rows, engine_order, rollback_commit
from repro.core.serving import DetectRequest, DetectionService
from repro.core.types import ClaimsDataset

CFG = CopyConfig(alpha=0.1, s=0.8, n=50.0)
TILE = 32


def _claims(rng, n, n_items, density=0.4):
    values = np.where(rng.random((n, n_items)) < density,
                      rng.integers(0, 4, (n, n_items)), -1).astype(np.int32)
    p = np.where(values == 0, 0.9,
                 np.where(values >= 0, 0.05, 0.0)).astype(np.float32)
    return values, rng.uniform(0.3, 0.95, n).astype(np.float32), p


def _world(seed=0, n_src=40, n_items=160):
    rng = np.random.default_rng(seed)
    values, acc, p = _claims(rng, n_src, n_items)
    return ClaimsDataset(values=values, accuracy=acc), p


def _check_slabs(index, n_buckets):
    """Every chunk's device slab, alone and in groups of two, against the
    host gather; returns the host chunking."""
    s_pad = -(-index.n_sources // TILE) * TILE
    ech = engine_order(index, n_buckets)
    ech.gather(index, row_capacity=s_pad)
    mesh = Mesh(np.array(jax.devices()[:1]), ("shards",))
    resident, nbytes = devchunks.upload(index.store, s_pad,
                                        NamedSharding(mesh, P()))
    assert nbytes > 0
    b, K = ech.width, ech.n_chunks
    assert K > 2
    for k in range(K):
        got = devchunks.gather(resident, ech.order[k * b:(k + 1) * b], 1,
                               jnp.int8)
        assert got.shape == (s_pad, 1, b) and got.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(got)[:, 0],
                                      ech.store.chunks[k])
    for k0 in range(0, K, 2):
        cols = np.full(2 * b, -1, np.int64)
        seg = ech.order[k0 * b:(k0 + 2) * b]
        cols[: len(seg)] = seg
        got = np.asarray(devchunks.gather(resident, cols, 2, jnp.int8))
        for i, k in enumerate(range(k0, k0 + 2)):
            want = (ech.store.chunks[k] if k < K
                    else np.zeros((s_pad, b), np.int8))
            np.testing.assert_array_equal(got[:, i], want)
    return ech


def _commit(index, ds, p, q=3, seed=5, density=0.4):
    rng = np.random.default_rng(seed)
    values, acc, pq = _claims(rng, q, ds.n_items, density)
    union = ClaimsDataset(values=np.vstack([ds.values, values]),
                          accuracy=np.concatenate([ds.accuracy, acc]))
    index.store.ensure_row_capacity(union.n_sources)
    return commit_rows(index, union, np.vstack([p, pq]), CFG, q,
                       compact=False)


@pytest.mark.parametrize("case", ["fresh", "committed", "rolled_back",
                                  "ragged"])
def test_device_slab_equals_the_host_gather(case):
    ds, p = _world(1)
    index = build_index(ds, p, CFG, chunk_entries=16)
    n_buckets = 8
    if case in ("committed", "rolled_back"):
        info = _commit(index, ds, p)
        assert index.store.n_rows == ds.n_sources + 3
        assert index.store.n_delta_entries > 0
        if case == "rolled_back":
            rollback_commit(index, info)
            assert index.store.n_delta_entries == 0
    if case == "ragged":
        n_buckets = 7
    ech = _check_slabs(index, n_buckets)
    if case == "ragged":
        assert ech.n_live % ech.width and (ech.order < 0).any()


def _stream(svc, batches):
    out = []
    for reqs in batches:
        futs = [svc.submit(r) for r in reqs]
        svc.flush()
        st = svc.engine.last_stats
        out.append(([f.result() for f in futs], st["rescored_pairs"],
                    st["gather"]))
    return out


def _batches(n_items, n_batches=3, per_batch=3, q=2, seed=7):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_batches):
        reqs = []
        for j in range(per_batch):
            values, acc, pq = _claims(rng, q, n_items,
                                      density=0.1 + 0.1 * i + 0.02 * j)
            reqs.append(DetectRequest(rid=i * per_batch + j, values=values,
                                      accuracy=acc, p_claim=pq))
        out.append(reqs)
    return out


def _service(ds, p, per_batch=3):
    return DetectionService(ds, p, CFG, mode="bucketed", tile=TILE,
                            devices=1, store_chunk_entries=16,
                            max_batch_requests=per_batch, result_cache=False)


def test_served_stream_is_equal_on_device_and_host(monkeypatch):
    ds, p = _world(2)
    batches = _batches(ds.n_items)
    dev = _stream(_service(ds, p), batches)
    monkeypatch.setattr(devchunks, "fits", lambda devices, nbytes: False)
    host = _stream(_service(ds, p), batches)
    for (rd, nd, gd), (rh, nh, gh) in zip(dev, host):
        assert (gd, gh) == ("device", "host")
        assert nd == nh
        for a, b in zip(rd, rh):
            np.testing.assert_array_equal(a.copying, b.copying)
            np.testing.assert_array_equal(a.pr_independent, b.pr_independent)
            np.testing.assert_array_equal(a.c_fwd, b.c_fwd)


def test_transient_commits_compile_the_device_gather_once(monkeypatch):
    ds, p = _world(3)
    svc = _service(ds, p)
    entries = []
    real = devchunks.upload

    def upload(store, s_pad, sharding):
        entries.append(store.n_delta_entries)
        return real(store, s_pad, sharding)

    monkeypatch.setattr(devchunks, "upload", upload)
    batches = _batches(ds.n_items, n_batches=6)
    _stream(svc, batches[:1])                      # warm
    jitted = (devchunks._zeros, devchunks._put, devchunks._gather)
    before = [f._cache_size() for f in jitted]
    got = _stream(svc, batches[1:])
    assert [g for _, _, g in got] == ["device"] * 5
    assert len(set(entries[1:])) == 5, entries     # the delta entries differ
    assert [f._cache_size() for f in jitted] == before
