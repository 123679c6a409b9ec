"""Compile the served path's device programs for a DESCRIBED TPU v5e.

No chip is attached here: `jax.experimental.topologies` describes a
`v5e:2x2` host and the TPU compiler, which is installed, compiles for it
by shape only (`on-chip-measurement` guide, section 2, third rehearsal).
What it refuses here it would refuse on the chip — block shapes against
the (8, 128) tiling, SMEM/VMEM use, a sort that takes minutes to compile.
Nothing runs, so these tests say nothing about results or times.

The shapes are the ones `chip_smoke.py`'s served phase produces: one
block's witness (~1k nodes, laid out a row a node) and a wave of seven (8k
nodes); the ecrecover program (256 and 2048 signature rows) takes 131 s and 104 s
to compile and stays a rehearsal (CHANGES.md, PR 24).

The topology is described inside a fixture (only the xdist worker that is
handed this file loads the TPU library), and every compile runs in this
process with the persistent compile cache off: a program compiled for a
described device is written to the cache but cannot be read back without
the chip.
"""

from __future__ import annotations

import os

import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """`shape(dims, dtype)` -> a ShapeDtypeStruct placed on the described
    chip 0, with the compile cache off for the module's lifetime."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("max_chunks", [1, 5])
@pytest.mark.parametrize("batch", [256, 8192])
def test_pallas_keccak_compiles_for_v5e(shape, max_chunks, batch):
    import jax.numpy as jnp

    from phant_tpu.ops.keccak_pallas import keccak256_chunked_pallas

    compiled = keccak256_chunked_pallas.lower(
        shape((batch, max_chunks, 34), jnp.uint32),
        shape((batch,), jnp.int32),
        max_chunks=max_chunks,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def pallas_is_the_keccak(monkeypatch):
    """`keccak256_chunked_auto` asks `jax.default_backend()`, which is the
    CPU in a rehearsal; steer it to the kernel a TPU would run."""
    import phant_tpu.ops.keccak_pallas as kp

    monkeypatch.setattr(kp, "pallas_available", lambda: True)


# (resident cap, padded node rows): one block's witness on a small table,
# and the served shape (the table at its cap of 2^20 rows, a lone block's
# 2,048 novel rows). The wave's (8192, 8192) compiled too in the PR 24
# rehearsal (24 s and 31 s), but would double this file's time.
_RESIDENT_SHAPES = [(1024, 1024), (1 << 20, 2048)]


def _table_shapes(shape, cap):
    import jax.numpy as jnp

    return (
        shape((cap, 8), jnp.uint32),
        shape((cap, 128), jnp.uint32),
        shape((cap, 9), jnp.uint32),
        shape((4 * cap,), jnp.int32),
        shape((cap, 2), jnp.uint32),
    )


@pytest.mark.parametrize("cap,rows", _RESIDENT_SHAPES)
def test_resident_update_compiles_for_v5e(shape, pallas_is_the_keccak, cap, rows):
    """The update program in the row form. Its temporaries are held to
    64 MB: the (cap, 17, 8) ref table it scattered into until PR 31 was
    re-laid out whole in every update, 4.3 GB of them at 2^20 rows; and
    nothing of it gathers (the probe loop's reads are in the loop's body)."""
    import jax
    import jax.numpy as jnp

    from phant_tpu.ops.witness_jax import WITNESS_MAX_CHUNKS
    from phant_tpu.ops.witness_resident import _update_impl

    update = jax.jit(
        _update_impl,
        static_argnames=("max_chunks",),
        donate_argnums=(0, 1, 2, 3, 4),
    )
    compiled = update.lower(
        *_table_shapes(shape, cap),
        shape((rows, WITNESS_MAX_CHUNKS * 34), jnp.uint32),
        shape((rows,), jnp.int32),
        shape((rows,), jnp.int32),
        max_chunks=WITNESS_MAX_CHUNKS,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "while" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (64 << 20)
    entry = text[text.index("ENTRY") :]
    assert " gather(" not in entry


@pytest.mark.parametrize("cap,rows", _RESIDENT_SHAPES[:1])
def test_resident_verdict_compiles_for_v5e(shape, cap, rows):
    """The verdict's sort-join: 513 s to compile at 1024 rows as one
    11-operand 9-key `lax.sort`, ~20 s as the scanned single-key sort it
    is now (witness_jax._referenced) — held to two minutes here so the
    slow form cannot come back unseen."""
    import time

    import jax
    import jax.numpy as jnp

    from phant_tpu.ops.witness_resident import _verdict_impl

    t0 = time.perf_counter()
    compiled = jax.jit(_verdict_impl).lower(
        *_table_shapes(shape, cap)[:3],
        shape((rows,), jnp.int32),
        shape((rows,), jnp.bool_),
        shape((rows,), jnp.int32),
        shape((8, 8), jnp.uint32),
    ).compile()
    assert time.perf_counter() - t0 < 120
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)


def test_the_lone_blocks_verdict_rung_compiles_for_v5e_at_the_shipped_cap(shape):
    """(16,384 rows, 1 block) against a table of 2^20 rows: the shape a
    block at the gas limit is launched on (PR 44), the widest the boot
    builds. Its temporaries (290 MiB here) grow with the rows' 17 references
    each; held to 1 GiB beside a table of 0.7 GB on a chip of 16."""
    import jax
    import jax.numpy as jnp

    from phant_tpu.ops.witness_resident import VERDICT_LADDER, _verdict_impl

    rows, blocks = max(VERDICT_LADDER)
    assert (rows, blocks) == (16384, 1)
    compiled = jax.jit(_verdict_impl).lower(
        *_table_shapes(shape, 1 << 20)[:3],
        shape((rows,), jnp.int32),
        shape((rows,), jnp.bool_),
        shape((rows,), jnp.int32),
        shape((blocks, 8), jnp.uint32),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)


def test_root_plan_compiles_for_v5e(shape, pallas_is_the_keccak):
    import jax.numpy as jnp

    from phant_tpu.crypto.keccak import keccak256
    from phant_tpu.mpt.mpt import Trie
    from phant_tpu.ops.mpt_jax import (
        MPT_MAX_CHUNKS,
        _hash_plan_fused,
        build_hash_plan,
    )

    trie = Trie()
    for i in range(1000):
        trie.put(keccak256(i.to_bytes(4, "big")), b"\x01" * 70)
    plan = build_hash_plan(trie)
    levels = tuple(
        tuple(shape(a.shape, a.dtype) for a in level) for level in plan.levels
    )
    compiled = _hash_plan_fused.lower(
        shape(plan.blob.shape, jnp.uint8), levels, max_chunks=MPT_MAX_CHUNKS
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rung", [0, 7])
def test_served_root_program_compiles_on_its_ladder_for_v5e(
    shape, pallas_is_the_keccak, rung
):
    """The serving root lane's program (PR 28) on the ladder's first rung,
    where a lone block's plan lies, and on its last (128 MiB of templates):
    one loop over the strips, the keccak kernel inside it."""
    import jax.numpy as jnp

    from phant_tpu.ops.mpt_jax import (
        MPT_MAX_CHUNKS,
        PLAN_LADDER,
        STRIP_HOLES,
        STRIP_ROWS,
        _hash_plan_outputs,
    )

    # a trace of these shapes made earlier in this process, on the CPU and
    # without the kernel (tests/test_root_ladder.py runs rung 0), would be
    # reused by `lower`
    from phant_tpu.ops.witness_jax import witness_digests

    witness_digests.clear_cache()
    _hash_plan_outputs.clear_cache()
    r = PLAN_LADDER[rung]
    rows = shape((r.steps, STRIP_ROWS), jnp.int32)
    holes = shape((r.steps, STRIP_HOLES), jnp.int32)
    compiled = _hash_plan_outputs.lower(
        shape((r.blob,), jnp.uint8),
        rows,
        rows,
        holes,
        holes,
        shape((), jnp.int32),
        shape((r.outs,), jnp.int32),
        max_chunks=MPT_MAX_CHUNKS,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "while" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)


def test_pallas_keccak_compiles_under_shard_map_for_four_v5e(topo, shape):
    """The mesh-sharded serving programs call the kernel under `shard_map`,
    whose vma check needs the kernel's output to say which mesh axes it
    varies over — the boot prewarm failed on four chips until it did."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from phant_tpu.ops.keccak_pallas import keccak256_chunked_pallas

    mesh = Mesh(np.array(topo.devices), ("dp",))
    rows = NamedSharding(mesh, P("dp"))
    program = jax.jit(
        jax.shard_map(
            lambda w, n: keccak256_chunked_pallas(w, n, max_chunks=5),
            mesh=mesh,
            in_specs=(P("dp"), P("dp")),
            out_specs=P("dp"),
        )
    )
    compiled = program.lower(
        jax.ShapeDtypeStruct((4096, 5, 34), jnp.uint32, sharding=rows),
        jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=rows),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
