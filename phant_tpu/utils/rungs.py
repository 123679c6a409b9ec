"""The shapes a served device program may be launched on.

A jitted program is built once for each shape of its arguments, and on
the v5e a build costs from 20 s (a table program) to two minutes
(`ecrecover_kernel`). Which requests share a wave is up to a 5 ms assembly
window, so a program keyed on the wave's own size met new shapes inside a
request for as long as the server ran (PERF.md section 7, fault 0c). Each
served program therefore declares, next to its kernel, a LADDER: the few
sizes it may be launched on (`secp256k1_jax.SIG_LADDER`,
`witness_resident.VERDICT_LADDER` and `ROW_LADDER`, `mpt_jax.PLAN_LADDER`).
A launch pads up to the next rung; a wave above the top rung is issued as
several launches of the top rung (`launches`), so the set is closed under
any wave, and a server on an accelerator builds the table's and the root
program's before its port answers (`engine_api/server.py`); `ecrecover`
has one rung, which the first request builds.

This module is the one place that gives a rung, and the one record of the
shapes run: `lanes.program_shapes{program=}` on /metrics. It imports no
jax (the scheduler of a cpu-backend server reads `pow2ceil` here).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

from phant_tpu.utils.trace import metrics


def pow2ceil(n: int) -> int:
    """The least power of two that is at least `n` (and at least 1)."""
    p = 1
    while p < n:
        p *= 2
    return p


def rung_of(ladder: Sequence[int], n: int) -> int:
    """The smallest rung of the ascending `ladder` that holds `n` rows.
    Above the top rung there is none: the caller splits first
    (`launches`)."""
    for rung in ladder:
        if rung >= n:
            return rung
    raise ValueError(f"{n} rows pass the top rung of {tuple(ladder)}")


def launches(ladder: Sequence[int], n: int) -> List[int]:
    """The rungs of the launches that together hold `n` independent rows:
    whole launches of the top rung, then the rest on its own rung. Their
    sum is what the rows are padded to; `n` of 0 is one launch of the
    bottom rung (callers that have nothing to launch do not ask)."""
    top = ladder[-1]
    full, rest = divmod(n, top)
    out = [top] * full
    if rest or not full:
        out.append(rung_of(ladder, rest))
    return out


#: program -> the shapes it has run on in this process: (rung, device, ...)
_shapes: Dict[str, set] = {}
_shapes_lock = threading.Lock()


def note_launch(program: str, rung, *where) -> None:
    """One launch of `program` on `rung` (a size, or the sizes of one index
    of a ladder): counted in `lanes.launches{program=,rung=}`, and the
    shape (`rung` with `where` it ran: the device, a table's rows) among
    those `lanes.program_shapes{program=}` counts. At every dispatch: a
    shape outside the boot's set shows as growth, and names its rung."""
    label = "x".join(map(str, rung)) if isinstance(rung, tuple) else str(rung)
    metrics.count("lanes.launches", program=program, rung=label)
    _set_gauge(program, (rung, *where))


def export_shapes(programs: Sequence[str]) -> None:
    """Set `lanes.program_shapes{program=}` for each of `programs`: a
    server calls this at its start, so the family is on /metrics before
    the first launch (0 then, on the CPU)."""
    for program in programs:
        _set_gauge(program, None)


def _set_gauge(program: str, shape: Optional[tuple]) -> None:
    with _shapes_lock:
        seen = _shapes.setdefault(program, set())
        if shape is not None:
            seen.add(shape)
        n = len(seen)
    metrics.gauge_set("lanes.program_shapes", n, program=program)


def shapes_of(program: str) -> frozenset:
    """The shapes `note_launch` has counted for `program` (tests)."""
    with _shapes_lock:
        return frozenset(_shapes.get(program, ()))


def note_split(program: str, n_launches: int) -> None:
    """A wave above the top rung went out as `n_launches` launches."""
    if n_launches > 1:
        metrics.count("lanes.split_launches", n_launches, program=program)
