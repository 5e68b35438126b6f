"""Device time of the block maker inside the streamed programs.

The maker of RandomPatchCifar's blocks (``FusedConvRectifyPool.
make_blocks_with_params``) is ONE Pallas call a group of filter banks
over all the rows it is handed (since PR 42 the kernel reads the images
and builds its patches in fast memory: no im2col operand, no loop over
row batches): in the trace the call is an op of its own name
(``fused_cifar_featurize.<n>``) and the ops around it have the
compiler's names. Where a block of all rows does not fit and the sweep
takes the rows in chunks (``cifar_aug_refit``), the loop over chunks is
a ``while`` op whose event encloses each chunk's call and the write of
what it made into the held block. So the maker's time is read as the
SMALLEST ``while`` event around each Pallas call, where that loop holds
no loop of its own (the scans over blocks and over a group's blocks
around it do); else, where there is no such loop, as the call itself.
Nothing is read, and None returned, where the trace holds no such call:
a program with another maker, a parent commit.
"""
from __future__ import annotations

import bisect
from typing import List, Optional, Sequence, Tuple

KERNEL = "fused_cifar_featurize"


def maker_events(trace, kernel: str = KERNEL
                 ) -> List[Tuple[str, float, float]]:
    """``(program, start_ns, end_ns)`` of every maker loop inside the
    window, each once."""
    if trace is None or not trace.devices:
        return []
    window = trace.window()
    dev = trace.devices[0]
    inside = [(n, s, e) for n, s, e in dev.ops
              if window is None or (s >= window[0] and e <= window[1])]
    calls = [(s, e) for n, s, e in inside if n.startswith(kernel)]
    # outer before inner where two start together: the latest of those
    # that started before a call and end after it is then the innermost
    loops = sorted(((s, e) for n, s, e in inside if n.startswith("while")),
                   key=lambda iv: (iv[0], -iv[1]))
    starts = [s for s, _ in loops]
    # properly nested: a loop holds another iff the next one starts in it
    leaf = [i + 1 == len(loops) or loops[i + 1][0] >= loops[i][1]
            for i in range(len(loops))]
    chosen = set()
    for cs, ce in calls:
        best = (cs, ce)
        for i in range(bisect.bisect_right(starts, cs) - 1, -1, -1):
            s, e = loops[i]
            if e >= ce:          # the innermost loop around the call
                if leaf[i]:
                    best = (s, e)
                break
        chosen.add(best)
    mods = sorted(dev.modules, key=lambda m: m[1])
    mod_starts = [m[1] for m in mods]
    out = []
    for s, e in sorted(chosen):
        i = bisect.bisect_right(mod_starts, s) - 1
        prog = mods[i][0] if i >= 0 and s < mods[i][2] else "?"
        out.append((prog, s, e))
    return out


def maker_seconds(run, programs: Optional[Sequence[str]] = None
                  ) -> Optional[float]:
    """Device seconds in the window of the maker's loops, in all
    programs or in those whose name starts with one of ``programs``."""
    events = maker_events(run.trace_data)
    if programs is not None:
        events = [ev for ev in events if ev[0].startswith(tuple(programs))]
    return sum(e - s for _, s, e in events) / 1e9 if events else None
