"""Jit'd public wrappers for the Pallas kernels.

``_default_interpret()`` is the one platform check that decides how every
Pallas kernel runs: compiled on a TPU, interpreted (the kernel body runs as
plain JAX ops — correctness validation) on any other backend.  The serving
backend and these wrappers resolve ``interpret`` through it.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.paged_attention import (  # noqa: F401  (re-exported)
    paged_attention as _paged, paged_gather, paged_kv_append,
    paged_kv_append_batch)


def _default_interpret() -> bool:
    """True unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool | None = None):
    if interpret is None:
        interpret = _default_interpret()
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                    interpret: bool | None = None):
    if interpret is None:
        interpret = _default_interpret()
    return _paged(q, k_pages, v_pages, block_tables, ctx_lens,
                  interpret=interpret)
