"""Operations and bytes that a kernel's algorithm needs, from its shapes.

Model FLOPs per token live with each family (``families/<family>.py``,
``flops_per_token``); this module holds the kernels'.
"""
from __future__ import annotations

from typing import Sequence, Tuple


def decode_attention(contexts: Sequence[int], *, n_heads: int,
                     n_kv_heads: int, head_dim: int, layers: int,
                     kv_bytes: int, q_bytes: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one-token attention for each slot in ``contexts``
    (its length: the positions it attends), in every layer.

    FLOPs: scores q.K and the weighted sum p.V, 2 x length x head_dim
    each, per query head.  Bytes: K and V up to each slot's length at the
    cache's stored dtype, plus q read and o written.  Positions past a
    slot's length are not needed, so a kernel that skips them is not
    charged for them.
    """
    ctx = float(sum(contexts))
    flops = 4.0 * layers * ctx * n_heads * head_dim
    kv = 2.0 * ctx * n_kv_heads * head_dim * kv_bytes
    qo = 2.0 * len(contexts) * n_heads * head_dim * q_bytes
    return flops, layers * (kv + qo)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
