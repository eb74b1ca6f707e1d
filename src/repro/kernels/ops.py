"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True on CPU (validation) and False on TPU
(real Mosaic lowering); model code selects kernels via
``ModelOpts(use_kernel=True)``.
"""
from __future__ import annotations

import jax

from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.expert_ffn import expert_ffn as _experts
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.ssd_scan import ssd_scan as _ssd


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, window=0, bq=128, bk=128,
                    interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _flash(q, k, v, causal=causal, window=window, bq=bq, bk=bk,
                  interpret=interpret)


def mha(q_bshd, k_bshd, v_bshd, *, causal=True, window=0, interpret=None):
    """(B,S,H,D)-layout convenience wrapper used by the model layer."""
    q = q_bshd.transpose(0, 2, 1, 3)
    k = k_bshd.transpose(0, 2, 1, 3)
    v = v_bshd.transpose(0, 2, 1, 3)
    o = flash_attention(q, k, v, causal=causal, window=window,
                        interpret=interpret)
    return o.transpose(0, 2, 1, 3)


def ssd(x, dt, A, Bm, Cm, D, *, chunk=128, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _ssd(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=interpret)


def decode_attention(q, k, v, length, layer=0, *, bk=None, precise=False,
                     interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _decode(q, k, v, length, layer, bk=bk, precise=precise,
                   interpret=interpret)


def expert_ffn(x, gates, wg, wi, wo, layer=0, *, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _experts(x, gates, wg, wi, wo, layer, interpret=interpret)
