"""Multi-chip / multi-host scaling (device meshes + sharded kernels)."""

from phant_tpu.parallel.mesh import (
    ecrecover_sharded,
    init_distributed,
    make_mesh,
    shard_map,
    witness_digests_sharded,
    witness_verify_fused_sharded,
    witness_verify_linked_sharded,
)

__all__ = [
    "ecrecover_sharded",
    "init_distributed",
    "make_mesh",
    "shard_map",
    "witness_digests_sharded",
    "witness_verify_fused_sharded",
    "witness_verify_linked_sharded",
]
