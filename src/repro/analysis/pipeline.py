"""Pass 1: DMA-pipeline hazard checker for the streamed gather kernels.

The streamed kernels in ``kernels/cvmm.py`` (fused w1, bare gather, the dW
streams) all drive their HBM->VMEM row DMAs through ONE control skeleton,
``cvmm.stream_schedule_step`` — the kernels bind real
``_gather_issue``/``_gather_wait`` callbacks and a traced grid index, while
this pass replays the SAME function with recording callbacks over every
concrete grid in a sweep. Because the skeleton is shared (not transcribed),
a schedule bug — a dropped wait, an off-by-one warmup, an unguarded prefetch
— changes both the kernels and the replay, and the replay proves it here
before a kernel ever corrupts data at runtime.

What is proven, per (pipeline family x depth x grid length x pass count):

  issue/wait pairing   every ``wait(t)`` matches the in-flight DMA of the same
                       tile in the same slot (the per-slot semaphore is FIFO;
                       a mismatched wait would consume another tile's signal)
  no slot overwrite    an ``issue`` never targets a slot whose previous DMA
                       has not been waited (the zero-fill + fresh DMA would
                       race the in-flight copy)
  compute reads waited data   the compute step of tile ``i`` reads the slot
                       that holds tile ``i``'s waited data, not a slot a later
                       prefetch already clobbered
  coverage             every tile 0..m_tiles-1 is issued exactly once and
                       waited exactly once per pass; no out-of-range tile is
                       ever issued (its chunk table does not exist)
  warmup/drain         boundary grids (``m_tiles < n_buffers``) stay legal,
                       and no DMA is left in flight at the end of a pass — the
                       dW kernels re-enter the stream once per outer pass, so
                       a leaked DMA would collide with the next warmup
  live bound           for the ``bounded`` families, at every live bound
                       ``n_live`` from 0 to the grid: steps at or past it run
                       no part of the schedule, no tile at or past it is ever
                       issued (it has no rows), and the live tiles keep every
                       invariant above

Depths swept: ``autotune.SUPPORTED_DEPTHS`` (2/3/4) — the union of what any
family's candidate enumerator can emit — for every entry in
``cvmm.STREAMED_PIPELINES``.

``check_live_blocks`` proves the other half of the live bound: the blocks
the Mosaic pipeline moves for the kernels' clamped index maps
(``cvmm.live_step`` / ``cvmm.live_tile``). On every (row, N) grid and every
bound, live steps keep their own blocks and every slack step repeats the
last live step's, so the pipeline fetches and writes back nothing after
the bound, and an output block is never left between two visits.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from ..kernels import autotune, cvmm
from .report import Finding

# Grid lengths swept: 1..MAX_TILES covers every warmup/drain regime — grids
# shorter than the deepest pipeline, equal to it, and long enough that the
# steady state (wait + prefetch) repeats.
MAX_TILES = 9
REENTRANT_PASSES = (1, 3)


def replay_stream(m_tiles: int, n_buffers: int, n_passes: int = 1,
                  n_live: Optional[int] = None) -> List[Tuple[str, int, int]]:
    """Replay ``cvmm.stream_schedule_step`` over a concrete grid.

    Returns the flat event list [(kind, tile, slot), ...] with kind one of
    "issue" / "wait" / "compute" / "pass_end" (tile = pass index, slot = -1
    for pass_end markers), exactly in the order the kernel executes them.
    With ``n_live`` the replay follows the bounded kernels: steps at or past
    the bound run nothing, and the schedule's tile count is the bound."""
    live = m_tiles if n_live is None else n_live
    events: List[Tuple[str, int, int]] = []

    def issue(t):
        events.append(("issue", int(t), int(cvmm.stream_slot(t, n_buffers))))

    def wait(t):
        events.append(("wait", int(t), int(cvmm.stream_slot(t, n_buffers))))

    def when(cond, fn):
        if cond:
            fn()

    for p in range(n_passes):
        for i in range(m_tiles):
            if i >= live:
                continue
            slot = cvmm.stream_schedule_step(i, live, n_buffers,
                                             issue=issue, wait=wait, when=when)
            events.append(("compute", i, int(slot)))
        events.append(("pass_end", p, -1))
    return events


def check_stream(m_tiles: int, n_buffers: int, n_passes: int = 1,
                 family: str = "stream",
                 n_live: Optional[int] = None) -> Tuple[List[Finding], int]:
    """Verify one replayed schedule against the hazard invariants. With
    ``n_live`` the tiles at or past the bound must never be issued."""
    live = m_tiles if n_live is None else n_live
    loc = (f"{family} depth={n_buffers} m_tiles={m_tiles}"
           + (f" n_live={n_live}" if n_live is not None else "")
           + (f" passes={n_passes}" if n_passes > 1 else ""))

    def bad(check: str, detail: str) -> Finding:
        return Finding("pipeline", check, loc, detail)

    findings: List[Finding] = []
    checks = 0
    in_flight = {}          # slot -> tile whose DMA has been issued, not waited
    resident = {}           # slot -> tile whose data has been waited (readable)
    issued = {}             # tile -> issue count, this pass
    waited = {}             # tile -> wait count, this pass

    for kind, t, slot in replay_stream(m_tiles, n_buffers, n_passes, n_live):
        if kind == "issue":
            checks += 3
            if not (0 <= t < m_tiles):
                findings.append(bad(
                    "issue-out-of-range",
                    f"issued tile {t}, but the grid has {m_tiles} tiles — "
                    f"its chunk table does not exist"))
            elif t >= live:
                findings.append(bad(
                    "issue-past-live",
                    f"issued tile {t}, at or past the live bound {live}: "
                    f"no step waits for it"))
            if slot in in_flight:
                findings.append(bad(
                    "slot-overwrite",
                    f"issue of tile {t} zero-fills slot {slot} while tile "
                    f"{in_flight[slot]}'s DMA into it is still in flight"))
            issued[t] = issued.get(t, 0) + 1
            if issued[t] > 1:
                findings.append(bad(
                    "double-issue",
                    f"tile {t} issued {issued[t]} times in one pass"))
            in_flight[slot] = t
            resident.pop(slot, None)          # zero-fill clobbers old data
        elif kind == "wait":
            checks += 1
            if in_flight.get(slot) != t:
                have = in_flight.get(slot)
                findings.append(bad(
                    "wait-mismatch",
                    f"wait for tile {t} on slot {slot}, but the slot holds "
                    + (f"tile {have}'s DMA" if have is not None
                       else "no in-flight DMA — the wait would hang or "
                            "consume a stale semaphore signal")))
            else:
                del in_flight[slot]
                resident[slot] = t
            waited[t] = waited.get(t, 0) + 1
        elif kind == "compute":
            checks += 1
            if resident.get(slot) != t:
                findings.append(bad(
                    "compute-unwaited",
                    f"compute of tile {t} reads slot {slot}, which holds "
                    f"{'tile %s' % resident[slot] if slot in resident else 'no waited data'}"))
        else:  # pass_end
            checks += 2
            if in_flight:
                findings.append(bad(
                    "leaked-dma",
                    f"pass ended with DMA(s) still in flight: "
                    f"{sorted(in_flight.items())} — the next warmup would "
                    f"overwrite them"))
            missing = [i for i in range(live)
                       if issued.get(i, 0) != 1 or waited.get(i, 0) != 1]
            if missing:
                findings.append(bad(
                    "coverage",
                    f"tiles not issued+waited exactly once this pass: "
                    f"{missing} (issued={issued}, waited={waited})"))
            issued, waited = {}, {}
    return findings, checks


def check_live_blocks(m_tiles: int, n_j: int,
                      n_live: int) -> Tuple[List[Finding], int]:
    """Replay the block indices of the clamped index maps over one (m_tiles,
    n_j) grid, row tile outermost (``cvmm.live_step``; the dW kernels' (b, m)
    grids are its n_j = 1 case per pass, through ``cvmm.live_tile``)."""
    loc = f"live blocks m_tiles={m_tiles} n_j={n_j} n_live={n_live}"
    findings: List[Finding] = []
    checks = 0
    seq = [tuple(int(v) for v in cvmm.live_step(i, j, n_live, n_j))
           for i in range(m_tiles) for j in range(n_j)]
    n_steps = min(n_live, m_tiles) * n_j
    checks += 3
    live_want = [(i, j) for i in range(n_live) for j in range(n_j)]
    if seq[:n_steps] != live_want:
        findings.append(Finding(
            "pipeline", "live-block-moved", loc,
            "a live step does not use its own blocks"))
    tail = set(seq[max(n_steps - 1, 0):])
    if len(tail) > 1:
        findings.append(Finding(
            "pipeline", "slack-block-moves", loc,
            f"slack steps change block indices ({sorted(tail)}): the "
            f"pipeline would fetch or write back blocks past the bound"))
    left = [b for k, b in enumerate(seq) if b in seq[:k] and seq[k - 1] != b]
    if left:
        findings.append(Finding(
            "pipeline", "block-revisited", loc,
            f"block {left[0]} is left and visited again: its first write "
            f"back would be overwritten"))
    return findings, checks


def check_pipeline() -> Tuple[List[Finding], int]:
    """Sweep every streamed-pipeline family at every supported depth (and,
    for the bounded ones, every live bound), then the clamped block indices
    of every grid."""
    findings: List[Finding] = []
    checks = 0
    for family, info in sorted(cvmm.STREAMED_PIPELINES.items()):
        passes = REENTRANT_PASSES if info["reentrant"] else (1,)
        for depth in autotune.SUPPORTED_DEPTHS:
            for m_tiles in range(1, MAX_TILES + 1):
                bounds = range(m_tiles + 1) if info["bounded"] else (None,)
                for n_live in bounds:
                    for n_passes in passes:
                        f, c = check_stream(m_tiles, depth, n_passes, family,
                                            n_live)
                        findings += f
                        checks += c
    for m_tiles in range(1, MAX_TILES + 1):
        for n_j in (1, 2, 3):
            for n_live in range(m_tiles + 1):
                f, c = check_live_blocks(m_tiles, n_j, n_live)
                findings += f
                checks += c
    return findings, checks
