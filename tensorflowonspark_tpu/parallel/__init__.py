"""Parallelism: device meshes, SPMD data parallelism, sharding helpers.

The tensor plane of the framework (SURVEY.md §5.8-2): XLA collectives over
ICI emitted by jit-compiled SPMD programs — no server objects, no NCCL.

Axes (mesh.AXES): ``dp`` (sync data parallel), ``fsdp`` (ZeRO-style param
sharding), ``tp`` (Megatron tensor parallel, tp.py), ``sp`` (ring
sequence parallel, sp.py), ``ep`` (expert parallel MoE, ep.py), ``pp``
(GPipe pipeline, pp.py).
"""

from tensorflowonspark_tpu.parallel.mesh import (  # noqa: F401
    MeshSpec,
    make_mesh,
    batch_sharding,
    replicated,
    shard_batch,
    shard_tree,
)
from tensorflowonspark_tpu.parallel.sp import (  # noqa: F401
    ring_attention,
    sequence_parallel_attention,
)
from tensorflowonspark_tpu.parallel.tp import (  # noqa: F401
    TRANSFORMER_TP_RULES,
    compose_fsdp,
    constrain,
    rule_shardings,
)
from tensorflowonspark_tpu.parallel.pp import (  # noqa: F401
    gpipe,
    pipeline_1f1b,
    stack_stages,
    stage_shardings,
)
from tensorflowonspark_tpu.parallel.ep import MoEMLP  # noqa: F401
