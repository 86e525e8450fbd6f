"""Pallas TPU kernel for batched decode attention with per-slot lengths.

The decode step (one token per slot against the resident KV cache) is
HBM-bandwidth-bound: its cost is dominated by streaming K/V out of HBM.
The XLA path (`ops.attention.attend`) must read the whole KV-length
bucket for every slot and mask the dead tail; this kernel instead
prefetches the per-slot true lengths as scalars and prunes at the block
level — a slot at position 600 in an 8192 bucket reads 5 blocks of K/V,
not 64. Pruned grid steps remap their BlockSpec index to the slot's last
live block, so Pallas's revisiting rule elides the DMA entirely.

Two generalisations beyond the q_len=1 bf16 original (docs/ROOFLINE.md):

- **Fused int8-KV dequant** (``k_scale``/``v_scale`` operands): the
  int8 KV tier's rows stream into VMEM still quantized and dequantize
  inside the kernel after the DMA, so int8 bytes — not bf16 — are what
  cross HBM on the attention read. Scales are per-row (granule
  ``token``: G=1, or ``head``: G=num_kv_heads, ops/kv_quant.py); the
  paged variant reads them in per-block-row pool layout.
- **Multi-token q blocks** (q [B, T, Nq, D], small static T): the
  spec-decode verify block (current + draft tokens) and any short
  decode block run through the kernel, causal WITHIN the block by
  per-query horizon masking. T=1 remains the plain decode step.

Per-step layout (one grid cell = one (slot, key block); all kv heads of
the block are processed in one cell, statically unrolled — Mosaic
requires the last two dims of every block to be (multiples of 8, 128) or
equal to the array dims, which rules out blocking the kv-head axis to 1):

    q      [B, Nkv, T*G, D]  VMEM block [1, Nkv, T*G, D]  (q rows
                             t-major per kv head: row = t * G + g)
    k, v   [B, S, Nkv, D]    VMEM block [1, blk, Nkv, D]  (cache layout,
                             no transpose of the resident cache)
    scales [B, S, G]         VMEM block [1, blk, G]       (int8 tier)
    out    [B, Nkv, T*G, D]  VMEM block [1, Nkv, T*G, D]

The kv-block axis is the innermost grid dimension, so the flash-style
online-softmax state (m, l, acc) lives in VMEM scratch and carries
across blocks of the same slot; it is initialised at block 0 and
normalised into the output at the last block.

Replaces capability the reference delegated to vLLM's PagedAttention
CUDA kernels (SURVEY.md §2: in-tree native components NONE; attention
lived in the external container). Single-device only: under a TP mesh
GSPMD cannot partition a custom kernel, so the engine keeps the XLA
path when a mesh is set (the all-reduce-fused XLA attention is the
right answer there anyway).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fasttalk_tpu.ops.pallas_backend import resolve_interpret

_NEG_INF = -1e30


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, *rest,
                   block_size: int, scale: float, group: int):
    """Shared online-softmax recurrence for the dense and paged kernels.

    ``rest`` is (o, m, l, acc) for the bf16 tier or
    (k_scale, v_scale, o, m, l, acc) for the fused-int8 tier — the two
    variants are distinct traced programs (the tier is static), so the
    arity switch costs nothing at run time.

    ``lengths[b]`` = keys visible to the LAST query of slot b's block
    (= first query position + T); earlier queries mask one key fewer
    each, which is exactly in-block causality. ``group`` = q heads per
    kv head; q rows are t-major, so row r is query t = r // group.
    """
    if len(rest) == 6:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref, vs_ref = None, None
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    nkv = q_ref.shape[1]
    tg = q_ref.shape[2]
    length = lengths_ref[b]
    num_live = pl.cdiv(length, block_size)  # blocks this slot must visit

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j < num_live)
    def _fold():
        key_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        # Per-query horizon: row r (query t = r // group) sees
        # length - (T - 1 - t) keys; T = tg // group. For T=1 this is
        # the original `key_pos < length` mask.
        t_idx = jax.lax.broadcasted_iota(
            jnp.int32, (tg, 1), 0) // group
        horizon = length - (tg // group - 1) + t_idx      # [tg, 1]
        live = key_pos < horizon                          # [tg, blk]
        for h in range(nkv):  # static unroll: one rank-2 MXU matmul each
            q = q_ref[0, h].astype(jnp.float32)       # [T*G, D]
            k = k_ref[0, :, h].astype(jnp.float32)    # [blk, D]
            v = v_ref[0, :, h].astype(jnp.float32)    # [blk, D]
            if ks_ref is not None:
                # Fused int8 dequant: rows arrived quantized; scale
                # them here, after the DMA. Granule token -> scale
                # column 0 for every head; granule head -> column h.
                si = h % ks_ref.shape[2]
                k = k * ks_ref[0, :, si][:, None]
                v = v * vs_ref[0, :, si][:, None]
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [T*G, blk]
            scores = jnp.where(live, scores, _NEG_INF)

            m_prev, l_prev = m_ref[h], l_ref[h]               # [T*G, 1]
            m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
            correction = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)                       # [T*G, blk]
            m_ref[h] = m_new
            l_ref[h] = l_prev * correction + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * correction + jnp.dot(
                p, v, preferred_element_type=jnp.float32)     # [T*G, D]

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


def _pack_q(q: jnp.ndarray, nkv: int):
    """[B, T, Nq, D] -> [B, Nkv, T*G, D] (t-major rows per kv head)."""
    b, t, nq, d = q.shape
    g = nq // nkv
    qg = q.reshape(b, t, nkv, g, d)
    return jnp.moveaxis(qg, 1, 2).reshape(b, nkv, t * g, d)


def _unpack_o(o: jnp.ndarray, t: int):
    """[B, Nkv, T*G, D] -> [B, T, Nq, D]."""
    b, nkv, tg, d = o.shape
    g = tg // t
    return jnp.moveaxis(o.reshape(b, nkv, t, g, d), 2, 1) \
        .reshape(b, t, nkv * g, d)


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def decode_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  lengths: jnp.ndarray, *, block_size: int = 128,
                  k_scale: jnp.ndarray | None = None,
                  v_scale: jnp.ndarray | None = None,
                  interpret: bool | None = None) -> jnp.ndarray:
    """GQA decode attention with block-level length pruning.

    q [B, Nq, D] (the single decode token per slot) or [B, T, Nq, D]
    (a short multi-token block, e.g. the spec-decode verify pass);
    k, v [B, S, Nkv, D] in cache layout; lengths [B] = number of valid
    keys per slot for the block's LAST query (first query position + T;
    for T=1 that is position + 1, unchanged from the single-token
    kernel). Earlier queries in the block see one key fewer each —
    in-block causality. Returns the same rank as ``q``. S must divide
    by block_size (KV-length buckets are powers of two >= 512).

    ``k_scale``/``v_scale`` [B, S, G] select the fused int8-dequant
    tier: k/v are int8 cache rows and dequantize INSIDE the kernel
    after the DMA (per-row scales, granule G = 1 or Nkv).
    """
    single = q.ndim == 3
    if single:
        q = q[:, None]
    b, t, nq, d = q.shape
    s, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    if s % block_size:
        raise ValueError(f"cache bucket {s} not divisible by {block_size}")
    interpret = resolve_interpret(interpret)
    nb = s // block_size
    qg = _pack_q(q, nkv)
    lengths = lengths.astype(jnp.int32)
    quantized = k_scale is not None

    def q_index(b_, j, lens):  # noqa: ARG001
        return (b_, 0, 0, 0)

    def kv_index(b_, j, lens):
        # Pruned blocks revisit the slot's last live block — same index
        # as the previous grid step, so no DMA is issued for them.
        num_live = pl.cdiv(lens[b_], block_size)
        return (b_, jnp.minimum(j, num_live - 1), 0, 0)

    def scale_index(b_, j, lens):
        num_live = pl.cdiv(lens[b_], block_size)
        return (b_, jnp.minimum(j, num_live - 1), 0)

    in_specs = [
        pl.BlockSpec((1, nkv, t * g, d), q_index),
        pl.BlockSpec((1, block_size, nkv, d), kv_index),
        pl.BlockSpec((1, block_size, nkv, d), kv_index),
    ]
    operands = [lengths, qg, k, v]
    if quantized:
        kvg = k_scale.shape[-1]
        in_specs += [pl.BlockSpec((1, block_size, kvg), scale_index),
                     pl.BlockSpec((1, block_size, kvg), scale_index)]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nkv, t * g, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((nkv, t * g, 1), jnp.float32),   # running max
            pltpu.VMEM((nkv, t * g, 1), jnp.float32),   # running denom
            pltpu.VMEM((nkv, t * g, d), jnp.float32),   # running numer
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=block_size,
                          scale=d ** -0.5, group=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, t * g, d), q.dtype),
        interpret=interpret,
    )(*operands)
    out = _unpack_o(out, t)
    return out[:, 0] if single else out


def _paged_decode_kernel(lengths_ref, tables_ref, q_ref, k_ref, v_ref,
                         *rest, block_size: int, scale: float,
                         group: int):
    """Identical softmax recurrence to ``_decode_kernel`` — the paged
    variant differs only in WHERE each grid step's K/V block comes
    from (the block-table index map below), so the per-slot length
    pruning and fused dequant carry over unchanged: grid step j of
    slot b masks by the slot's true length and pruned steps elide
    their DMA."""
    _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, *rest,
                   block_size=block_size, scale=scale, group=group)


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def decode_attend_paged(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        lengths: jnp.ndarray, tables: jnp.ndarray, *,
                        block_size: int,
                        k_scale: jnp.ndarray | None = None,
                        v_scale: jnp.ndarray | None = None,
                        interpret: bool | None = None) -> jnp.ndarray:
    """GQA decode attention over a PAGED block pool: the per-slot
    length pruning of ``decode_attend`` extended to walk block lists
    (KV_LAYOUT=paged, docs/KVCACHE.md "Paged tier").

    q [B, Nq, D] or [B, T, Nq, D] (multi-token verify block); k, v are
    the flat device pool [P = num_blocks * block_size, Nkv, D];
    lengths [B] = valid keys per slot for the block's LAST query;
    tables [B, nb] = pool block id holding each slot's logical block
    (nb * block_size is the call's KV bucket). Both scalar operands
    prefetch, so the index map routes each grid step's DMA to
    ``tables[b, j]`` — logically contiguous attention over physically
    scattered blocks, no gather materialisation. Steps past a slot's
    live length revisit its last live block and elide the DMA, exactly
    like the dense kernel.

    ``k_scale``/``v_scale`` [P, G] are the pool's per-block-row scale
    arrays (int8 tier): they ride the SAME block-table index map as
    k/v, so each grid step DMAs its block's scale rows alongside the
    int8 rows and dequantizes in VMEM.
    """
    single = q.ndim == 3
    if single:
        q = q[:, None]
    b, t, nq, d = q.shape
    p, nkv = k.shape[0], k.shape[1]
    g = nq // nkv
    if p % block_size:
        raise ValueError(f"pool rows {p} not divisible by {block_size}")
    interpret = resolve_interpret(interpret)
    nb = tables.shape[1]
    kb = k.reshape(p // block_size, block_size, nkv, d)
    vb = v.reshape(p // block_size, block_size, nkv, d)
    qg = _pack_q(q, nkv)
    lengths = lengths.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    quantized = k_scale is not None

    def q_index(b_, j, lens, tabs):  # noqa: ARG001
        return (b_, 0, 0, 0)

    def kv_index(b_, j, lens, tabs):
        # Walk the slot's block list; pruned steps revisit the last
        # live block (same index as the previous step → no DMA).
        num_live = pl.cdiv(lens[b_], block_size)
        return (tabs[b_, jnp.minimum(j, num_live - 1)], 0, 0, 0)

    def scale_index(b_, j, lens, tabs):
        num_live = pl.cdiv(lens[b_], block_size)
        return (tabs[b_, jnp.minimum(j, num_live - 1)], 0, 0)

    in_specs = [
        pl.BlockSpec((1, nkv, t * g, d), q_index),
        pl.BlockSpec((1, block_size, nkv, d), kv_index),
        pl.BlockSpec((1, block_size, nkv, d), kv_index),
    ]
    operands = [lengths, tables, qg, kb, vb]
    if quantized:
        kvg = k_scale.shape[-1]
        in_specs += [pl.BlockSpec((1, block_size, kvg), scale_index),
                     pl.BlockSpec((1, block_size, kvg), scale_index)]
        operands += [k_scale.reshape(p // block_size, block_size, kvg),
                     v_scale.reshape(p // block_size, block_size, kvg)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nkv, t * g, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((nkv, t * g, 1), jnp.float32),   # running max
            pltpu.VMEM((nkv, t * g, 1), jnp.float32),   # running denom
            pltpu.VMEM((nkv, t * g, d), jnp.float32),   # running numer
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, block_size=block_size,
                          scale=d ** -0.5, group=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, t * g, d), q.dtype),
        interpret=interpret,
    )(*operands)
    out = _unpack_o(out, t)
    return out[:, 0] if single else out
