"""Blocked linear-algebraic support kernel: S = (U @ U) ∘ U on the MXU.

This is Algorithm 1 of the paper executed the way a systolic array wants it:
the symmetric 0/1 adjacency is tiled into (B, B) VMEM blocks and the
support matrix block S[i,j] accumulates Σ_k U[i,k] @ U[k,j] on the MXU, with
the elementwise ∘ U[i,j] mask applied on the final k step.  It is the
*dense/coarse* counterpart against which the fine-grained edge-tile kernel
is compared: FLOP-rich and perfectly load balanced, but O(V³/B) work
independent of sparsity — which is exactly the trade the paper's Figure 4
exposes (dense linear-algebra wins only on small, dense graphs).

Grid: (V/B, V/B, V/B) with k innermost ("arbitrary"); f32 accumulation in a
VMEM scratch block (ids are counts ≤ degree, exactly representable in f32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["support_dense_pallas"]


def _kernel(u_ik_ref, u_kj_ref, u_ij_ref, out_ref, acc_ref, *, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        u_ik_ref[...], u_kj_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _finalize():
        out_ref[...] = acc_ref[...] * u_ij_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def support_dense_pallas(
    u_sym: jax.Array, *, block: int = 128, interpret: bool | None = None
) -> jax.Array:
    """S = (U @ U) ∘ U for a dense 0/1 symmetric adjacency (f32).

    V must be a multiple of ``block`` (the ops.py wrapper pads; padded
    rows/cols are all-zero so they contribute nothing).  ``interpret=None``
    runs the Pallas interpreter off a TPU only.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    v = u_sym.shape[0]
    if u_sym.shape != (v, v):
        raise ValueError(f"expected square adjacency, got {u_sym.shape}")
    if v % block:
        raise ValueError(f"V={v} not a multiple of block={block}")
    steps = v // block

    return pl.pallas_call(
        functools.partial(_kernel, k_steps=steps),
        grid=(steps, steps, steps),
        in_specs=[
            pl.BlockSpec((block, block), lambda i, j, k: (i, k)),  # U[i,k]
            pl.BlockSpec((block, block), lambda i, j, k: (k, j)),  # U[k,j]
            pl.BlockSpec((block, block), lambda i, j, k: (i, j)),  # mask
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((v, v), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)],
        interpret=interpret,
    )(u_sym.astype(jnp.float32), u_sym.astype(jnp.float32), u_sym.astype(jnp.float32))
