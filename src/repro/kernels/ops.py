"""Dispatching wrappers for the Pallas kernels.

The one place that decides whether a kernel is interpreted: on TPU the
kernels always run compiled; everywhere else they run in interpret mode
(correct, slow) when ``use_kernel`` asks for them, and otherwise the jnp
oracle runs.  The backend is read from JAX, so a chip host never takes
the interpreter.  This is the layer models/benchmarks import.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gat_attention import gat_attention as _gat_attention
from repro.kernels.gather_spmm import gather_spmm as _gather_spmm
from repro.kernels.sddmm import sddmm as _sddmm
from repro.kernels.spmm import spmm as _spmm


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def spmm(h, w, nbr, mask, use_kernel: bool = False, **kw):
    if not (use_kernel or on_tpu()):
        return ref.spmm_ref(h, w, nbr, mask)
    return _spmm(h, w, nbr, mask, interpret=not on_tpu(), **kw)


def gather_spmm(h, table, w, nbr, mask, use_kernel: bool = False, **kw):
    if not (use_kernel or on_tpu()):
        return ref.gather_spmm_ref(h, table, w, nbr, mask)
    return _gather_spmm(h, table, w, nbr, mask, interpret=not on_tpu(), **kw)


def gat_attention(q, k, nbr, mask, heads: int = 1, use_kernel: bool = False,
                  **kw):
    if not (use_kernel or on_tpu()):
        return ref.gat_attention_ref(q, k, nbr, mask, heads)
    return _gat_attention(q, k, nbr, mask, heads=heads,
                          interpret=not on_tpu(), **kw)


def sddmm(q, k, nbr, mask, use_kernel: bool = False, **kw):
    if not (use_kernel or on_tpu()):
        return ref.sddmm_ref(q, k, nbr, mask)
    return _sddmm(q, k, nbr, mask, interpret=not on_tpu(), **kw)


def flash_attention(q, k, v, causal: bool = True, use_kernel: bool = False,
                    **kw):
    if not (use_kernel or on_tpu()):
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return _flash(q, k, v, causal=causal, interpret=not on_tpu(), **kw)
