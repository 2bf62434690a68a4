"""Pallas TPU grouped expert FFN (``moe_gmm``): the SwiGLU MLPs of the
experts a device holds, each applied to exactly the rows routed to it.

Rows arrive grouped by expert, each group starting on a multiple of the
row tile ``tm`` (``models/moe.py`` lays them out), so every tile of rows
belongs to one expert.  The grid walks only the tiles that hold rows
(its length is the routed tile count, a traced scalar), and each tile's
expert is scalar-prefetched into the weights' block index: consecutive
tiles of one expert keep its weights resident, and an expert no row was
routed to is never read from HBM.  Per tile, gate and up projections, the
SiLU product and the down projection run back to back in VMEM, with
float32 accumulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the three (D, F) weight blocks, double-buffered, plus a row tile's
# activations: 37 MB at DeepSeek-V2-Lite's widths, of the v5e's 128 MiB
_VMEM_LIMIT = 96 * 1024 * 1024


def _kernel(tile_group_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    del tile_group_ref
    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    o_ref[...] = jnp.dot(h, wd_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def moe_gmm(x, w_gate, w_up, w_down, tile_group, n_tiles, *, tm: int,
            interpret: bool = False):
    """x: (M, D) rows, tile t = rows [t*tm, (t+1)*tm) routed to expert
    ``tile_group[t]`` for t < ``n_tiles`` (traced int32 scalar); w_gate,
    w_up: (E, D, F); w_down: (E, F, D).  Returns (M, D) in x's dtype:
    ``silu(x W_gate) * (x W_up) W_down`` of its tile's expert for every
    row of the first ``n_tiles`` tiles; rows past them are not written."""
    M, D = x.shape
    E, _, F = w_gate.shape
    rows = lambda t, grp: (t, 0)
    expert = lambda t, grp: (grp[t], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tm, D), rows),
                  pl.BlockSpec((None, D, F), expert),
                  pl.BlockSpec((None, D, F), expert),
                  pl.BlockSpec((None, F, D), expert)],
        out_specs=pl.BlockSpec((tm, D), rows),
    )
    return pl.pallas_call(
        _kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="moe_gmm",
    )(tile_group.astype(jnp.int32), x, w_gate, w_up, w_down)
