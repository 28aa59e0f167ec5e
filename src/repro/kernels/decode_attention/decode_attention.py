"""Pallas TPU kernel: single-token decode attention (flash-decoding style).

The decode hot spot: one query token per sequence against a long KV cache.
The kernel tiles the batch across the grid; within a program the cache is
streamed in fixed-size sequence tiles with a running (max, denominator,
accumulator) carry — scores never materialize beyond one (H, T) tile in
VMEM.  The causal/length mask comes from a per-sequence ``pos`` scalar.

PSAM framing: the KV cache is the read-only large structure (written once
per step elsewhere, streamed here); the O(B·H·D) attention state is the
small memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..lowering import resolve_interpret

NEG_INF = -1e30
DEFAULT_TILE_BATCH = 4
DEFAULT_SEQ_TILE = 128


def _kernel(q_ref, k_ref, v_ref, pos_ref, out_ref, *, seq_tile: int):
    q = q_ref[...]            # (TB, H, D)
    pos = pos_ref[...]        # (TB,) int32 — #valid cache entries per seq
    TB, H, D = q.shape
    S = k_ref.shape[1]
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qf = q.astype(jnp.float32) * scale

    nt = S // seq_tile

    def body(t, carry):
        m, l, acc = carry
        kt = k_ref[:, pl.dslice(t * seq_tile, seq_tile)]  # (TB, T, H, D)
        vt = v_ref[:, pl.dslice(t * seq_tile, seq_tile)]
        s = jnp.einsum("bhd,bthd->bht", qf, kt.astype(jnp.float32))
        kv_pos = t * seq_tile + jnp.arange(seq_tile)
        mask = kv_pos[None, None, :] < pos[:, None, None]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bht,bthd->bhd", p, vt.astype(jnp.float32))
        acc = acc * corr[..., None] + pv
        return m_new, l, acc

    m0 = jnp.full((TB, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((TB, H), jnp.float32)
    a0 = jnp.zeros((TB, H, D), jnp.float32)
    m, l, acc = lax.fori_loop(0, nt, body, (m0, l0, a0))
    out_ref[...] = (acc / jnp.maximum(l[..., None], 1e-30)).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tile_batch", "seq_tile", "interpret")
)
def decode_attention_pallas(
    q: jnp.ndarray,    # (B, H, D)
    k: jnp.ndarray,    # (B, S, H, D)
    v: jnp.ndarray,    # (B, S, H, D)
    pos: jnp.ndarray,  # (B,) int32 — valid cache length per sequence
    *,
    tile_batch: int = DEFAULT_TILE_BATCH,
    seq_tile: int = DEFAULT_SEQ_TILE,
    interpret: bool | None = None,
) -> jnp.ndarray:
    interpret = resolve_interpret(interpret, kernel="decode_attention")
    B, H, D = q.shape
    S = k.shape[1]
    st = min(seq_tile, S)
    pad_s = (-S) % st
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    TB = min(tile_batch, B)
    pad_b = (-B) % TB
    if pad_b:
        q = jnp.pad(q, ((0, pad_b), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, pad_b), (0, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad_b), (0, 0), (0, 0), (0, 0)))
        pos = jnp.pad(pos, (0, pad_b), constant_values=1)
    Bp, Sp = B + pad_b, S + pad_s
    grid = (Bp // TB,)

    out = pl.pallas_call(
        functools.partial(_kernel, seq_tile=st),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TB, H, D), lambda i: (i, 0, 0)),
            pl.BlockSpec((TB, Sp, H, D), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((TB, Sp, H, D), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((TB,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((TB, H, D), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, H, D), q.dtype),
        interpret=interpret,
    )(q, k, v, pos)
    return out[:B]
