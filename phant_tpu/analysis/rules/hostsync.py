"""HOSTSYNC: accidental device->host syncs on the verification hot path.

The north-star loop (batched keccak over witness nodes, post-state roots,
vmapped ecrecover) only sustains its throughput while the device pipeline
stays asynchronous: a stray `.item()`, `int(device_value)` or
`np.asarray(device_value)` inside the hot path forces a blocking
round-trip per call — invisible in review, catastrophic in the profiler
(the exact failure mode MHOT's hash-pipeline analysis warns about).

Scope: every function reachable (phant_tpu/analysis/symbols.py call
graph) from the hot-path entry points — `stateless.execute_stateless`
and `WitnessEngine.verify_batch` by default. Flags:

  * `.item()` calls (always — a scalar pull is a sync no matter the type);
  * `.block_until_ready()` calls (an explicit sync; legitimate ones are
    probes and carry a disable annotation with the reason);
  * `jax.device_get(...)`;
  * `int()` / `bool()` / `float()` / `np.asarray()` / `np.array()` over a
    device-tainted expression (see rules/_taint.py).

Intentional syncs — the timed `keccak.host_readback` phase, the one-shot
link probe — are annotated `# phantlint: disable=HOSTSYNC` with a reason,
which doubles as in-code documentation of where the honest syncs live.
"""

from __future__ import annotations

import ast
from typing import Iterator, Sequence, Tuple

from phant_tpu.analysis.core import Finding, Rule, iter_calls
from phant_tpu.analysis.rules._taint import (
    Taint,
    is_jax_call,
    resolve_external,
    snippet,
)
from phant_tpu.analysis.symbols import Project, _dotted

DEFAULT_ENTRIES: Tuple[str, ...] = (
    "phant_tpu.stateless.execute_stateless",
    "phant_tpu.ops.witness_engine.WitnessEngine.verify_batch",
    # mesh serving (PR 7): the per-device executor loop and the routing/
    # megabatch entries are the serving hot path — a stray sync in a lane
    # stalls one chip's whole pipeline
    "phant_tpu.serving.mesh_exec.MeshExecutorPool.submit",
    "phant_tpu.serving.mesh_exec.MeshExecutorPool._run_executor",
    "phant_tpu.serving.mesh_exec.MeshExecutorPool.run_megabatch",
    # device-resident intern table (PR 8): the whole point of the
    # resident route is that dispatch enqueues with ZERO host sync —
    # a reintroduced readback in the scan/assign/enqueue path puts the
    # host<->device link back on the per-batch critical path and silently undoes
    # the architecture (the resolve stage's honest syncs are annotated)
    "phant_tpu.ops.witness_engine.WitnessEngine.begin_batch",
    "phant_tpu.ops.witness_resident.ResidentTable.dispatch",
    # streaming witness ingestion (PR 9): the prefetch stage exists to
    # take work OFF the serving critical path — the engine pre-scan and
    # the scheduler's prefetch worker must never pull a device scalar
    # (a sync there re-serializes the 4th stage against the device and
    # silently turns the overlap win into a stall)
    "phant_tpu.ops.witness_engine.WitnessEngine.prefetch_batch",
    "phant_tpu.serving.scheduler.VerificationScheduler._prefetch_run",
    # batched post-state roots (PR 11): plan lowering (the merge the
    # prefetch stage runs) and the root_many dispatch path exist to
    # enqueue the merged program with ZERO host sync — a reintroduced
    # `.item()`/readback in the level loop puts a blocking round trip
    # back on every coalesced post root (the resolve stage's honest
    # readback is annotated)
    "phant_tpu.ops.root_engine.RootEngine.prefetch_batch",
    "phant_tpu.ops.root_engine.RootEngine.root_many",
    # coalesced sender recovery (PR 14): the sig lane's merge (the row
    # concat + limb encode the prefetch stage runs) and the sig_many
    # dispatch path exist to enqueue the merged ecrecover with ZERO host
    # sync — a reintroduced `.item()`/readback in the merge loop puts a
    # blocking round trip back on every coalesced recovery (the resolve
    # stage's honest sender readback is annotated)
    "phant_tpu.ops.sig_engine.SigEngine.prefetch_batch",
    "phant_tpu.ops.sig_engine.SigEngine.sig_many",
    # the lanes' measured stages (PR 26, utils/trace.lane_stage): the
    # brackets around begin_batch in the pipeline handoff and around
    # resolve_batch in the resolve worker are clock readings by design; a
    # reintroduced `.item()`/readback there would put a device sync on
    # EVERY pipelined batch under the banner of observability (the mesh
    # lane loop, _run_executor above, already covers its own brackets)
    "phant_tpu.serving.scheduler.VerificationScheduler._pipeline_handoff",
    "phant_tpu.serving.scheduler.VerificationScheduler._resolve_run",
    # pluggable commitment schemes (PR 12): the binary backend's witness
    # pack loop (full-subtree node collection) and proof-path walk feed
    # the serving differential spans and the fixture-translation
    # harness — pure host-bytes work by design; a reintroduced `.item()`
    # or device readback in these walks would put a sync inside the
    # per-block witness generation loop
    "phant_tpu.commitment.binary.BinaryScheme.collect_nodes",
    "phant_tpu.commitment.binary.BinaryScheme.proof_nodes",
    # historical replay (PR 18): segment plan lowering runs on the
    # replay pipeline's prefetch stage — it groups K blocks' root plans
    # into structure-sharing runs and stacks the payload blobs for ONE
    # vmapped device program, all host-side shape work by design; a
    # reintroduced `.item()`/readback there re-serializes segment N+1's
    # prep against segment N's device work (the resolve stage's honest
    # per-root readback lives in resolve_segment_roots, off this list)
    "phant_tpu.replay.lowering.lower_segment_plans",
)

_SCALAR_BUILTINS = ("int", "bool", "float")


class HostSyncRule(Rule):
    name = "HOSTSYNC"
    description = "device->host sync inside the hot verification path"

    def __init__(self, entries: Sequence[str] = DEFAULT_ENTRIES):
        self.entries = tuple(entries)

    def run(self, project: Project) -> Iterator[Finding]:
        for qualname in sorted(project.reachable(self.entries)):
            fi = project.functions.get(qualname)
            if fi is None:
                continue
            mi = project.modules.get(fi.module)
            if mi is None:
                continue
            taint = Taint(project, mi, fi.node, taint_params=fi.jitted)
            for call in iter_calls(fi.node):
                func = call.func
                if isinstance(func, ast.Attribute):
                    if func.attr == "item" and not call.args:
                        yield self.finding(
                            project,
                            mi,
                            call,
                            f"`{snippet(call)}` forces a device->host sync "
                            "(.item()) on the hot path",
                            context=qualname,
                        )
                        continue
                    if func.attr == "block_until_ready":
                        yield self.finding(
                            project,
                            mi,
                            call,
                            f"`{snippet(call)}` blocks on device completion "
                            "on the hot path",
                            context=qualname,
                        )
                        continue
                d = _dotted(func)
                if d is not None:
                    full = resolve_external(mi, d)
                    if full == "jax.device_get":
                        yield self.finding(
                            project,
                            mi,
                            call,
                            f"`{snippet(call)}` copies a device value to "
                            "host on the hot path",
                            context=qualname,
                        )
                        continue
                    if full in ("numpy.asarray", "numpy.array") and any(
                        taint.tainted(a) for a in call.args
                    ):
                        yield self.finding(
                            project,
                            mi,
                            call,
                            f"`{snippet(call)}` materializes a device value "
                            "on host (blocking readback) on the hot path",
                            context=qualname,
                        )
                        continue
                if (
                    isinstance(func, ast.Name)
                    and func.id in _SCALAR_BUILTINS
                    and func.id not in mi.imports
                    and func.id not in mi.functions
                    and any(taint.tainted(a) for a in call.args)
                ):
                    yield self.finding(
                        project,
                        mi,
                        call,
                        f"`{snippet(call)}` pulls a device scalar to host "
                        f"({func.id}() is a blocking sync) on the hot path",
                        context=qualname,
                    )
