"""Entry chunks gathered on the device (DESIGN.md §6).

The engine's chunk store is the base store's columns permuted into
p-sorted order (``index.engine_order``). On the unsharded 1-D tile mesh the
host no longer builds that permuted copy: it ships the base store's
incidence as it already sits, once per pass, and every scan group gathers
its ``(S_pad, Gc, b)`` slab out of the resident copy on the device.

The resident copy is held entries × rows (a group is then a row gather and
one transpose, which the TPU does in a fraction of a millisecond, where a
gather along the minor axis of an int8 array takes over ten times as
long). Its entry axis is padded to a power-of-two number of base chunks, so
a pass whose commit added delta entries gathers from the same shape as the
last, and nothing compiles after the first pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Sharding

#: Base chunks written to the resident copy per device call.
UPLOAD_BATCH = 64


def _slots(n_chunks: int) -> int:
    """Base-chunk slots of the resident copy: the next power of two."""
    return 1 << max(int(n_chunks) - 1, 0).bit_length()


def _width(store) -> int:
    """Entry slots per base chunk: the store's chunk width, or the one
    chunk's own width where a store holds a single narrower chunk."""
    return max((c.shape[1] for c in store.chunks), default=0)


def _upload_rows(store, s_pad: int) -> int:
    """Rows shipped per chunk: ``s_pad`` where the chunk arrays hold that
    many (rows past ``n_rows`` read zero), else the live rows. Shipping the
    tile-padded rows keeps the upload's shape the same for every batch
    size; the capacity slack beyond ``s_pad`` is never shipped."""
    return s_pad if store.capacity >= s_pad else store.n_rows


def device_nbytes(store, s_pad: int) -> int:
    """Device bytes the resident copy of ``store`` and one upload call in
    flight take (per device)."""
    w = _width(store)
    return (_slots(store.n_chunks) * s_pad
            + min(UPLOAD_BATCH, _slots(store.n_chunks)) * s_pad) * w


def fits(devices, nbytes: int) -> bool:
    """Whether ``nbytes`` more fit on each device, by ``memory_stats()``;
    True where a device reports no limit (the CPU)."""
    for d in devices:
        st = d.memory_stats()
        if st and "bytes_limit" in st:
            if st["bytes_limit"] - st.get("bytes_in_use", 0) < nbytes:
                return False
    return True


@functools.partial(jax.jit, static_argnums=(0, 1))
def _zeros(shape, sharding):
    return jax.lax.with_sharding_constraint(jnp.zeros(shape, jnp.int8),
                                            sharding)


@functools.partial(jax.jit, donate_argnums=0)
def _put(buf, blocks, col0):
    """Write base chunks ``blocks`` (each ``(rows, w)``), transposed and
    row-padded, at entry ``col0`` of the resident ``(E_pad, S_pad)``."""
    x = jnp.stack(blocks)
    x = jnp.pad(x, ((0, 0), (0, buf.shape[1] - x.shape[1]), (0, 0)))
    x = jnp.transpose(x, (0, 2, 1)).reshape(-1, buf.shape[1])
    return jax.lax.dynamic_update_slice(buf, x, (col0, 0))


def upload(store, s_pad: int, sharding: Sharding):
    """Ship ``store``'s incidence to the devices of ``sharding``.

    Returns ``(resident, nbytes)``: the ``(E_pad, s_pad)`` int8 copy, entry
    ``e`` in row ``e`` (rows past the store's entries are zero), and the
    bytes shipped to each device. A chunk narrower than the store's width
    (the last one, a partial delta chunk) is zero-padded on the host.
    """
    w = _width(store)
    rows = _upload_rows(store, s_pad)
    n = store.n_chunks
    slots = _slots(n)
    batch = min(UPLOAD_BATCH, slots)
    buf = _zeros((slots * w, s_pad), sharding)
    pad = None
    nbytes = 0
    for c0 in range(0, n, batch):
        blocks = []
        for c in range(c0, min(c0 + batch, n)):
            blk = store.chunks[c][:rows]
            if blk.shape[1] != w:
                full = np.zeros((rows, w), np.int8)
                full[:, : blk.shape[1]] = blk
                blk = full
            nbytes += blk.nbytes
            blocks.append(blk)
        blocks = jax.device_put(blocks, sharding)
        if len(blocks) < batch:
            if pad is None:
                pad = _zeros((rows, w), sharding)
            blocks += [pad] * (batch - len(blocks))
        buf = _put(buf, tuple(blocks), np.int32(c0 * w))
    return buf, nbytes


@functools.partial(jax.jit, static_argnums=(2, 3))
def _gather(resident, cols, gc, dtype):
    n = resident.shape[0]
    g = jnp.take(resident, jnp.where(cols < 0, n, cols), axis=0,
                 mode="fill", fill_value=0)
    return (g.reshape(gc, -1, resident.shape[1]).transpose(2, 0, 1)
            .astype(dtype))


def gather(resident, cols: np.ndarray, gc: int, dtype):
    """One scan group's ``(S_pad, gc, b)`` slab: column ``j`` of chunk
    ``i`` is resident entry ``cols[i * b + j]``, a zero column where that
    is ``-1``. Bit-equal to the host gather of the same columns."""
    return _gather(resident, np.asarray(cols, np.int32), int(gc),
                   jnp.dtype(dtype))


__all__ = ["UPLOAD_BATCH", "device_nbytes", "fits", "gather", "upload"]
