"""Set-up as the program's compile observatory saw it.

Every record of ``keystone_tpu.observability.compilelog`` says how long
jax traced, lowered and was inside the backend for one observed call,
whether the persistent cache answered (``cache``), how long the read
took, what a process with an empty cache would have compiled for
(``cold_s``) and when (``t_start``, ``perf_counter`` seconds: the clock
of ``benchmarks.harness.T_START``). The six ``.setup`` readers sum the
records that START inside set-up, ``T_START <= t_start < T_START +
run.setup_s``: the window compiles nothing in a sound run, and the
reference's own compiles come after it. Read once a run (kept in
``run.facts``), and the table is said once. ``None`` where there is
nothing sound to read: records without a time (a parent commit's
program), or a tail that dropped some (the observatory keeps the last
512 and counts all).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmarks.harness import T_START

FACT = "setup_compiles"


def records(run) -> Optional[List[Dict[str, Any]]]:
    if FACT not in run.facts:
        run.facts[FACT] = _read(run)
    return run.facts[FACT]


def total(run, *fields: str) -> Optional[float]:
    """Sum of the named fields over set-up's records."""
    mine = records(run)
    if mine is None:
        return None
    return float(sum(r[f] for r in mine for f in fields))


def _read(run) -> Optional[List[Dict[str, Any]]]:
    from keystone_tpu.observability.compilelog import compile_observatory

    obs = compile_observatory()
    tail = obs.tail()
    if not tail or any("t_start" not in r for r in tail):
        return None   # a program whose records carry no time
    if obs.count_total() > len(tail):
        run.say(f"set-up compiles: the observatory counted "
                f"{obs.count_total()} records and holds {len(tail)}: not read")
        return None
    mine = [r for r in tail
            if T_START <= r["t_start"] < T_START + run.setup_s]
    say_table(run, mine, len(tail) - len(mine))
    return mine


def say_table(run, mine: List[Dict[str, Any]], later: int) -> None:
    missed = sum(r["cache_misses"] for r in mine)
    run.say(f"set-up compiles: {len(mine)} records in set-up ({later} after "
            f"it), {sum(r['cache_hits'] for r in mine)} programs read from "
            f"the cache, {missed} missed it, "
            f"{sum(r['cache'] == 'off' for r in mine)} records with no "
            f"cache; the five slowest (seconds: wall = trace + lower + "
            f"backend; cache read; cold):")
    for r in sorted(mine, key=lambda r: -r["wall_s"])[:5]:
        name = f"{r.get('program') or '?'} [{r['name']}]"
        name = name if len(name) <= 40 else name[:37] + "..."
        run.say(f"  {name:<40} {r['wall_s']:8.3f} = {r['trace_s']:7.3f} + "
                f"{r['lower_s']:7.3f} + {r['backend_s']:8.3f}; "
                f"{r['cache']:<4} {r['cache_read_s']:8.3f}; "
                f"{r['cold_s']:8.3f}")
