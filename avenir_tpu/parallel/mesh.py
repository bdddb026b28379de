"""Mesh construction + sharded aggregation helpers.

Design (SURVEY §2.12): avenir's only parallel axes are (a) independent rows
-> a 'data' mesh axis, and (b) the all-pairs distance grid of KNN -> an
optional second 'model' axis sharding the train side. Reductions that the
reference routed through the Hadoop shuffle become segment_sum per shard +
psum over 'data'; the resulting model tensors are small and replicated.

Multi-host scale-out: jax.distributed gives one process per host; the same
mesh spans all hosts' devices and the same psum rides ICI within a slice and
DCN across slices — no NCCL/MPI analog needed, XLA owns the transport.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off. Every mesh kernel
    in this package routes through here so the choice is made once."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def data_mesh(devices: Optional[Sequence] = None,
              model_parallel: int = 1) -> Mesh:
    """A (data[, model]) mesh over the given (default: all) devices.

    model_parallel > 1 carves a second axis used to shard the train side of
    all-pairs distance work; everything else uses pure data parallelism.
    """
    devs = np.array(devices if devices is not None else jax.devices())
    n = devs.size
    if model_parallel > 1:
        if n % model_parallel != 0:
            raise ValueError(
                f"device count {n} is not divisible by "
                f"model_parallel={model_parallel}; pass a device list whose "
                "size is a multiple of the model axis (or model_parallel=1)"
            )
        grid = devs.reshape(n // model_parallel, model_parallel)
        return Mesh(grid, (DATA_AXIS, MODEL_AXIS))
    return Mesh(devs.reshape(n), (DATA_AXIS,))


def row_spec(mesh: Mesh) -> P:
    return P(DATA_AXIS)


def shard_rows(mesh: Mesh, arr: jax.Array, pad_value=0, axis: int = 0
               ) -> jax.Array:
    """Place a host array row-sharded over the data axis, padding the row
    count up to shard divisibility with `pad_value` rows. The rows are
    axis 0 unless `axis` says otherwise."""
    n_shards = mesh.shape[DATA_AXIS]
    rem = (-arr.shape[axis]) % n_shards
    if rem:
        shape = arr.shape[:axis] + (rem,) + arr.shape[axis + 1:]
        arr = np.concatenate(
            [np.asarray(arr), np.full(shape, pad_value, dtype=arr.dtype)],
            axis=axis)
    return jax.device_put(
        arr, NamedSharding(mesh, P(*[None] * axis, DATA_AXIS)))


def row_mask(mesh: Mesh, n_valid: int, n_padded: int) -> jax.Array:
    """1.0 for real rows, 0.0 for divisibility padding."""
    mask = (np.arange(n_padded) < n_valid).astype(np.float32)
    return jax.device_put(mask, NamedSharding(mesh, P(DATA_AXIS)))


def replicated(mesh: Mesh, arr) -> jax.Array:
    return jax.device_put(jnp.asarray(arr), NamedSharding(mesh, P()))


def sharded_keyed_count(
    mesh: Mesh,
    count_fn: Callable[..., jax.Array],
):
    """Wrap a per-shard counting kernel into a mesh program.

    count_fn(*row_sharded_args) -> count pytree computed on the local rows.
    Returns a jitted function over row-sharded inputs whose outputs are the
    global (psum'd over 'data') counts, replicated on every device. This is
    the canonical 'mapper + shuffle + reducer' collapse: XLA inserts an
    all-reduce over ICI where Hadoop ran a disk shuffle.
    """
    def wrapped(*args):
        local = count_fn(*args)
        return jax.tree.map(lambda t: jax.lax.psum(t, DATA_AXIS), local)

    fn = shard_map(wrapped, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P())
    return jax.jit(fn)
