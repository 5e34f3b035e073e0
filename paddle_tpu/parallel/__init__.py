"""paddle_tpu.parallel (exposed as paddle_tpu.distributed) — the
distributed suite (SURVEY.md §2.3), TPU-native over jax.sharding +
jax.lax collectives on ICI/DCN.
"""
import jax as _jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None, **kw):
    """`jax.shard_map`, through one door: every manual-collective module
    in this package calls this. Defined before the submodule imports
    below so `from . import shard_map` works during package init."""
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return _jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, **kw)


from . import env  # noqa: F401,E402
from .env import (  # noqa: F401
    init_parallel_env, get_rank, get_world_size, ParallelEnv, barrier,
    is_initialized, global_mesh,
)
from .collective import (  # noqa: F401
    ReduceOp, Group, new_group, all_reduce, all_gather, broadcast, reduce,
    scatter, all_to_all, all_reduce_coalesced, wait,
)
from .comm_extras import (  # noqa: F401
    all_gather_object, reduce_scatter, isend, irecv, send, recv, stream,
)
from . import moe_utils as utils  # noqa: F401
from .topology import (  # noqa: F401
    CommunicateTopology, HybridCommunicateGroup,
    get_hybrid_communicate_group, set_hybrid_communicate_group,
)
from .strategy import DistributedStrategy  # noqa: F401
from .data_parallel import DataParallel, shard_batch  # noqa: F401
from .recompute import recompute  # noqa: F401
from .auto_tuner import (  # noqa: F401
    ClusterSpec, CostModel, ModelSpec, Strategy, StrategyTuner,
    TunedResult, tune,
)
from . import fleet  # noqa: F401


def spawn(func, args=(), nprocs=-1, **kwargs):
    """paddle.distributed.spawn parity: under jax single-controller SPMD a
    single process drives all chips, so spawn degenerates to a direct call
    (multi-host launch is `python -m paddle_tpu.distributed.launch`)."""
    func(*args)
