"""horovod_tpu — a TPU-native distributed training framework with the
capability surface of Horovod (reference: leewyang/horovod).

Unchanged single-device training scripts gain data-parallel scaling via
``init()`` + collective ops + ``DistributedOptimizer`` wrappers, exactly
as in the reference — but the engine is built for TPU: ranks bind to
devices of a ``jax.sharding.Mesh``, collectives are cached compiled XLA
programs (``lax.psum``/``all_gather``/``all_to_all``/``psum_scatter``)
riding ICI/DCN, and fusion packs gradients into single compiled
collectives instead of NCCL launches on CUDA fusion buffers.

Typical use (mirrors ``import horovod.torch as hvd``)::

    import horovod_tpu as hvd
    hvd.init()
    ...
    avg_grad = hvd.allreduce(grad, op=hvd.Average)
"""

from .version import __version__

from .common.basics import (  # noqa: F401
    init, shutdown, is_initialized,
    rank, size, local_rank, local_size, cross_rank, cross_size,
    is_homogeneous, bind_rank, unbind_rank,
    mpi_threads_supported, mpi_built, gloo_built, nccl_built, ddl_built,
    ccl_built, cuda_built, rocm_built, xla_built, tpu_built,
    start_timeline, stop_timeline, dump_trace,
    metrics, start_metrics_server,
)
from . import telemetry  # noqa: F401
from .core import integrity  # noqa: F401
from .common.exceptions import (  # noqa: F401
    HorovodInternalError, HostsUpdatedInterrupt,
)
from .common.process_sets import (  # noqa: F401
    ProcessSet, add_process_set, remove_process_set, global_process_set,
)
from .core.message import (  # noqa: F401
    Average, Sum, Adasum, Min, Max, Product, ReduceOp,
)
from .ops.api import (  # noqa: F401
    allreduce, allreduce_async, allreduce_, allreduce_async_,
    grouped_allreduce, grouped_allreduce_async,
    allgather, allgather_async, grouped_allgather, grouped_allgather_async,
    broadcast, broadcast_async, broadcast_, broadcast_async_,
    alltoall, alltoall_async,
    reducescatter, reducescatter_async,
    grouped_reducescatter, grouped_reducescatter_async,
    barrier, join, synchronize, poll,
    broadcast_object, allgather_object,
)
from .ops.compression import Compression  # noqa: F401
from .ops.compiled import (  # noqa: F401
    compiled_allreduce, compiled_alltoall, compiled_grouped_allreduce,
    CompiledAlltoall, CompiledGroupedAllreduce, CompiledPredict,
    TopologyHint, make_compiled_train_step,
)
from .ops.grad_hook import reduce_in_backward  # noqa: F401
from . import serving  # noqa: F401
from .runner.thread_launcher import run  # noqa: F401
