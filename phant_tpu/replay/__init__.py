"""Historical chain replay as a megabatch workload (catch-up sync).

The product surface ROADMAP calls "how fast can a fresh node catch up":
`ReplayEngine` holds a whole chain segment and drives it through the
serving stack's witness/root/sig lanes at far-past-serving batch shapes
— one merged ecrecover launch per segment, witness megabatches against
per-lane resident intern tables, K block-state roots per vmapped device
program — with a prefetch pipeline that builds segment N+1's inputs
under segment N's EVM execution. `python -m phant_tpu.replay
<fixture-chain> --segment K` is the CLI face.
"""

from phant_tpu.replay.engine import (
    DEFAULT_SEGMENT_BLOCKS,
    BlockVerdict,
    ReplayEngine,
    ReplayReport,
    replay_fixture,
)
from phant_tpu.replay.fixture import (
    ReplayFixture,
    attach_witnesses,
    build_synthetic_chain,
    load_fixture,
    save_fixture,
)

__all__ = [
    "DEFAULT_SEGMENT_BLOCKS",
    "BlockVerdict",
    "ReplayEngine",
    "ReplayReport",
    "ReplayFixture",
    "attach_witnesses",
    "build_synthetic_chain",
    "load_fixture",
    "replay_fixture",
    "save_fixture",
]
