"""Storage backends for KV block pools.

A block's payload is one ndarray ``[layers, 2(kv), block_size, kv_heads,
head_dim]``.  Backends expose uniform read/write by block id; batched
variants amortize dispatch (the transfer engine always moves batches).

(Reference: lib/llm/src/block_manager/storage.rs — System/Pinned/Device/
Disk/Null backends; here Device is a jax array in HBM, Host is numpy in
DRAM — effectively pinned for TPU DMA purposes — Disk is a memmap.)
"""

from __future__ import annotations

from pathlib import Path

import numpy as np


def block_shape(num_layers: int, block_size: int, kv_heads: int, head_dim: int) -> tuple:
    return (num_layers, 2, block_size, kv_heads, head_dim)


def block_nbytes(num_layers, block_size, kv_heads, head_dim, dtype) -> int:
    return int(np.prod(block_shape(num_layers, block_size, kv_heads, head_dim))) * np.dtype(dtype).itemsize


class Storage:
    """Uniform block storage interface."""

    num_blocks: int

    def read(self, block_id: int) -> np.ndarray:
        return self.read_batch([block_id])[0]

    def write(self, block_id: int, data: np.ndarray) -> None:
        self.write_batch([block_id], data[None])

    def read_batch(self, block_ids: list[int]) -> np.ndarray:
        raise NotImplementedError

    def write_batch(self, block_ids: list[int], data: np.ndarray) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class NullStorage(Storage):
    """Metadata-only: accepts writes, reads zeros.  For pool/offload logic
    tests with no memory cost."""

    def __init__(self, num_blocks: int, shape: tuple, dtype=np.float32):
        self.num_blocks = num_blocks
        self.shape = shape
        self.dtype = np.dtype(dtype)

    def read_batch(self, block_ids: list[int]) -> np.ndarray:
        return np.zeros((len(block_ids), *self.shape), self.dtype)

    def write_batch(self, block_ids: list[int], data: np.ndarray) -> None:
        pass


class HostStorage(Storage):
    """Host DRAM pool (G2)."""

    def __init__(self, num_blocks: int, shape: tuple, dtype=np.float32):
        self.num_blocks = num_blocks
        self.shape = shape
        self._data = np.zeros((num_blocks, *shape), dtype)

    def read_batch(self, block_ids: list[int]) -> np.ndarray:
        return self._data[np.asarray(block_ids, np.int64)].copy()

    def write_batch(self, block_ids: list[int], data: np.ndarray) -> None:
        self._data[np.asarray(block_ids, np.int64)] = data


class DiskStorage(Storage):
    """Local SSD pool (G3) via np.memmap (host-mediated; the TPU analog of
    the reference's GDS-backed disk tier)."""

    def __init__(self, num_blocks: int, shape: tuple, dtype=np.float32, *, path: str | Path):
        self.num_blocks = num_blocks
        self.shape = shape
        self.path = Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._data = np.memmap(
            self.path, dtype=dtype, mode="w+", shape=(num_blocks, *shape)
        )

    def read_batch(self, block_ids: list[int]) -> np.ndarray:
        return np.asarray(self._data[np.asarray(block_ids, np.int64)])

    def write_batch(self, block_ids: list[int], data: np.ndarray) -> None:
        self._data[np.asarray(block_ids, np.int64)] = data

    def flush(self) -> None:
        self._data.flush()

    def close(self) -> None:
        self.flush()
        del self._data


class DeviceStorage(Storage):
    """Device HBM pool (G1): one jax array, batched gather/scatter transfers
    (jax.device_put/get replace cudaMemcpy; on TPU these ride the host DMA
    path, and same-mesh moves stay on ICI)."""

    def __init__(self, num_blocks: int, shape: tuple, dtype=None, *, device=None, sharding=None):
        import jax
        import jax.numpy as jnp

        self._jax = jax
        self._jnp = jnp
        self.num_blocks = num_blocks
        self.shape = shape
        dtype = dtype or jnp.float32
        self._data = jnp.zeros((num_blocks, *shape), dtype)
        if sharding is not None:
            self._data = jax.device_put(self._data, sharding)
        elif device is not None:
            self._data = jax.device_put(self._data, device)
        # on TPU the Pallas block-copy kernels move blocks with pipelined
        # HBM↔VMEM DMAs (the block_copy.cu replacement, SURVEY.md §2.2);
        # XLA gather/scatter serves other backends and sharded pools
        if sharding is None and jax.default_backend() == "tpu":
            from dynamo_tpu.ops.pallas.block_copy import gather_blocks, scatter_blocks

            self._write = lambda pool, ids, blocks: scatter_blocks(
                pool, blocks.astype(pool.dtype), ids
            )
            self._read = gather_blocks
        else:
            self._write = jax.jit(
                lambda pool, ids, blocks: pool.at[ids].set(blocks.astype(pool.dtype)),
                donate_argnums=(0,),
            )
            self._read = jax.jit(lambda pool, ids: pool[ids])

    @property
    def array(self):
        return self._data

    def read_batch(self, block_ids: list[int]) -> np.ndarray:
        ids = self._jnp.asarray(np.asarray(block_ids, np.int32))
        return np.asarray(self._read(self._data, ids))

    def write_batch(self, block_ids: list[int], data: np.ndarray) -> None:
        ids = self._jnp.asarray(np.asarray(block_ids, np.int32))
        self._data = self._write(self._data, ids, self._jnp.asarray(data))
