"""Operations and bytes of the held-experts FFN (``expert_ffn``), from what
the server's decode steps routed, and the growth of the server's routing
counters over a traced window.

``BatchedServer`` counts, over its occupied slots and every layer,
``routed_rows`` ((token, expert) pairs routed), ``held_rows`` (those that
landed on an expert held here) and ``expert_loads`` ((layer, held expert)
pairs that took at least one row), and writes them with ``step`` as stats
of each ``serve.step`` span, as they stand at the step's entry
(``programspans``).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import programspans

programspans.install()

COUNTERS = ("step", "routed_rows", "held_rows", "expert_loads")


def growth(trace, names: Sequence[str] = COUNTERS
           ) -> Optional[Dict[str, int]]:
    """Each counter's growth from the window's first ``serve.step`` span to
    its last; None with fewer than two, or where the spans lack a counter
    (a program that does not count routes)."""
    if not trace:
        return None
    a, b = trace.window
    steps = [s[3] for s in programspans.program(trace)
             if s[0] == "serve.step" and a <= s[1] < b]
    if len(steps) < 2 or any(n not in steps[0] for n in names):
        return None
    return {n: int(steps[-1][n]) - int(steps[0][n]) for n in names}


def expert_ffn(held_rows: int, expert_loads: int, *, d_model: int,
               d_ff: int, weight_bytes: int, row_bytes: int
               ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the held experts' SwiGLU FFNs for the rows routed.

    FLOPs: 2 x 3 x d x f for each held row actually routed.  Bytes: each
    held expert's three matrices once for every (layer, expert) that took a
    row, plus each routed row read and its result written.  Rows of tokens
    an expert was not routed to, and experts that took no row, are not
    needed, so a kernel that skips them is not charged for them.
    """
    flops = 2.0 * 3 * d_model * d_ff * held_rows
    weights = 3.0 * d_model * d_ff * weight_bytes * expert_loads
    rows = 2.0 * d_model * row_bytes * held_rows
    return flops, weights + rows
