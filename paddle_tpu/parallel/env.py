"""Distributed environment + device mesh management.

Parity: `python/paddle/distributed/parallel.py:104 init_parallel_env` (+
TCPStore rendezvous `distributed/store/tcp_store.h:120`, NCCL comm-id
bootstrap `platform/gen_comm_id_helper.cc`).

TPU-native (SURVEY.md §5.8): `jax.distributed.initialize` is the
coordination service (subsumes TCPStore / gen_nccl_id / gloo barriers); the
"world" is jax's global device set. Within one host, the N local TPU chips
are N "ranks" under SPMD — collectives compile onto ICI. `global_mesh()`
builds the `jax.sharding.Mesh` every parallel layer shards over.
"""
from __future__ import annotations

import os

import numpy as np
import jax

_initialized = False
_mesh_cache = {}


class ParallelEnv:
    """paddle.distributed.ParallelEnv parity."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def local_rank(self):
        return get_rank() % max(jax.local_device_count(), 1)

    @property
    def nranks(self):
        return get_world_size()

    @property
    def dev_id(self):
        return self.local_rank


def init_parallel_env():
    """Initialise multi-host coordination when env vars are present.

    Single-host multi-chip needs no rendezvous (jax sees all local chips);
    multi-host uses jax.distributed (coordinator address from
    PADDLE_MASTER / MASTER_ADDR env, paddle-launch-style env parsing —
    `launch/context/__init__.py`)."""
    global _initialized
    if _initialized:
        return ParallelEnv()
    coord = os.environ.get("MASTER_ADDR") or os.environ.get("PADDLE_MASTER")
    n_nodes = int(os.environ.get("PADDLE_NNODES",
                                 os.environ.get("WORLD_SIZE_NODES", "1")))
    already = False
    try:
        from jax._src import distributed as _jd
        already = _jd.global_state.client is not None
    except Exception:
        pass
    if coord and n_nodes > 1 and not already:
        # jax.distributed.initialize refuses to run once a backend is
        # initialised. Importing paddle_tpu does not do that; the first
        # jax computation does — so this must run before any.
        port = os.environ.get("MASTER_PORT", "8476")
        pid = int(os.environ.get("PADDLE_NODE_RANK",
                                 os.environ.get("NODE_RANK", "0")))
        try:
            # CPU multi-process collectives need the gloo implementation
            # (the TestDistBase-style localhost two-rank tests)
            jax.config.update("jax_cpu_collectives_implementation",
                              "gloo")
        except Exception:
            pass
        jax.distributed.initialize(
            coordinator_address=f"{coord}:{port}",
            num_processes=n_nodes, process_id=pid)
    _initialized = True
    return ParallelEnv()


def get_rank(group=None):
    """Process-level rank. Under single-controller SPMD this is the jax
    process index (0 on one host)."""
    try:
        return jax.process_index()
    except Exception:
        return 0


def get_world_size(group=None):
    """Number of devices participating in data parallelism by default."""
    if group is not None:
        return group.nranks
    try:
        return jax.device_count()
    except Exception:
        return 1


def device_count():
    return jax.device_count()


def is_initialized():
    return _initialized


def device_grid(devices, shape):
    """`devices` as an ndarray of `shape`, ready for a `Mesh`.

    A whole TPU slice goes through `jax.experimental.mesh_utils`, which
    lays every mesh axis along physical ICI rings: on a 2x2 v5e tray the
    ids 0,1,2,3 in order are NOT a ring (1 -> 2 is the diagonal), the
    ring is 0,1,3,2. A part of a slice, or the CPU test mesh, has no
    topology to honour and is reshaped as given."""
    devices = list(devices)
    if devices[0].platform == "tpu" and len(devices) == jax.device_count():
        from jax.experimental import mesh_utils
        return mesh_utils.create_device_mesh(tuple(shape), devices=devices)
    return np.array(devices).reshape(shape)


def global_mesh(axes=None):
    """The framework-wide device mesh.

    axes: dict name->size (ordered), e.g. {"dp":2, "pp":2, "mp":2}.
    Defaults to a pure-dp mesh over all devices. Cached per shape."""
    if axes is None:
        axes = {"dp": jax.device_count()}
    key = tuple(axes.items())
    if key not in _mesh_cache:
        names = tuple(axes.keys())
        sizes = tuple(axes.values())
        n = int(np.prod(sizes))
        devs = np.array(jax.devices()[:n]).reshape(sizes)
        _mesh_cache[key] = jax.sharding.Mesh(devs, names)
    return _mesh_cache[key]


def barrier(group=None):
    """Host barrier: a tiny psum over all devices forces a sync point."""
    import jax.numpy as jnp
    x = jnp.ones((jax.device_count(),))
    jax.block_until_ready(
        jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")(x))
