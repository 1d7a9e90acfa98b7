"""Pallas TPU attention kernel for SHORT sequences (L ≤ ~256).

BASELINE.md §encoder-mfu names the attention core as the encoder's remaining
bandwidth sink: at L=128 the XLA sdpa path moves the materialized
[B, H, L, L] score tensor through HBM several times, and the stock pallas
flash-attention kernel loses outright (26% vs 42% MFU — its multi-block
pipeline is built for long L). This kernel exploits that MiniLM-class
ingest sequences FIT IN VMEM: one grid step loads a (block_b, L, D) q/k/v
tile in the model's NATIVE flat layout (no [B,H,L,hd] transpose — measured
to erase the win), unrolls the heads as 64-wide column slices, and computes
scores→mask→softmax→context per head entirely on-chip. One HBM read of
q/k/v and one write of ctx — the information-theoretic floor.

Numerics: f32 score accumulation, mask fill −1e30 (finite: fully-padded rows
give uniform probs, not NaN), f32 softmax, bf16 context matmul inputs with
f32 accumulation.

``attention_short_flat`` returns ``None`` (caller uses the XLA path) when the
shapes don't meet the tile constraints — a selection made from what the code
can see. A failure to trace, lower or compile the kernel is an error and
propagates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(
    jax.jit, static_argnames=("n_heads", "scale", "block_b", "interpret")
)
def _attention_short_impl(
    q, k, v, mask, n_heads: int, scale: float, block_b: int, interpret: bool = False
):
    from jax.experimental import pallas as pl

    B, L, D = q.shape
    hd = D // n_heads

    def kernel(q_ref, k_ref, v_ref, m_ref, o_ref):
        key_mask = m_ref[:]  # [bB, L]
        # static unroll over heads (Mosaic matmuls allow one batch dim);
        # heads live as 64-wide column slices of the flat activation, and
        # each head's context stores straight to its output columns (a
        # gather-then-concatenate would hold a second full tile in VMEM)
        for h in range(n_heads):
            sl = slice(h * hd, (h + 1) * hd)
            qq = q_ref[:, :, sl]  # [bB, L, hd]
            kk = k_ref[:, :, sl]
            vv = v_ref[:, :, sl]
            scores = jax.lax.dot_general(
                qq, kk, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale  # [bB, L, L]
            scores = jnp.where(key_mask[:, None, :], scores, -1e30)
            mx = jnp.max(scores, axis=-1, keepdims=True)
            e = jnp.exp(scores - mx)
            probs = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(qq.dtype)
            o_ref[:, :, sl] = jax.lax.dot_general(
                probs, vv, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ).astype(o_ref.dtype)

    spec = pl.BlockSpec((block_b, L, D), lambda b: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(B // block_b,),
        in_specs=[spec, spec, spec, pl.BlockSpec((block_b, L), lambda b: (b, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, mask)


#: scoped-VMEM budget for one grid step (bytes): q/k/v/o tiles
#: (double-buffered by the pipeline) + the per-head f32 score tile. The
#: hardware limit is 16 MiB; Mosaic compile failures surface at OUTER-jit
#: compile time, as a crash of that launch, so the gate must be sufficient,
#: not optimistic. block_b=16 at (L=128, D=384) sits at 14.7 MB under this
#: budget.
_VMEM_BUDGET = 15 * 1024 * 1024


def _vmem_estimate(block_b: int, L: int, D: int) -> int:
    tiles = 4 * 2 * block_b * L * D * 2  # q,k,v,o bf16, double-buffered
    scores = block_b * L * L * 4 * 2  # f32 scores + softmax temporaries
    return tiles + scores


def attention_short_flat(q, k, v, mask, n_heads: int, scale: float, block_b: int = 16):
    """Flat-layout attention: [B, L, D] q/k/v + [B, L] key mask →
    [B, L, D] context, heads as D/n_heads column groups. Returns ``None``
    when the kernel doesn't apply (caller takes the XLA path). The gate must
    reject anything that could fail MOSAIC COMPILATION — those errors raise
    at the enclosing jit's compile and fail the launch."""
    B, L, D = q.shape
    hd = D // n_heads
    if L > 128 or L % 8 != 0 or hd % 64 != 0 or D % 128 != 0:
        return None  # only shapes in the measured envelope (L ≤ 128)
    # largest VMEM-feasible block that divides the batch. Mosaic's mask-tile
    # rule needs the batch block divisible by 8 (sublane) — or equal to the
    # whole batch, which B=1 queries satisfy.
    candidates = [bb for bb in (block_b, 8) if bb % 8 == 0] + ([B] if B < 8 else [])
    for bb in candidates:
        if B % bb == 0 and _vmem_estimate(bb, L, D) <= _VMEM_BUDGET:
            block_b = bb
            break
    else:
        return None
    return _attention_short_impl(q, k, v, mask, n_heads, scale, block_b)
