"""Pallas TPU kernels: matmul with int8 weights, dequantized in VMEM.

Why: decode with int8 weight-only quantization should be HBM-bound on
the int8 bytes, but XLA's lowering of ``(x @ q.astype(bf16)) * s``
materialises the converted bf16 weight, so int8 saves almost nothing
(measured on v5e: 4.99 ms/step int8-XLA vs 5.49 bf16 at batch 16 for
the 1B model — a 9% win where bytes promise 45%). These kernels DMA the
int8 tile to VMEM, convert as the MXU consumes it, and scale the small
accumulator instead of the huge weight.

The r2 kernel used a (bk=512, bn=512) 2-D grid whose q-blocks were
*strided* row fragments (512-byte contiguous runs); measured 237 GB/s —
slower in wall time than just streaming bf16. The fix is block shape:
every block here is a run of **whole rows**, so each DMA is one
contiguous span and streams at HBM rate.

Two layouts:
- ``int8_matmul``:  y[M,N] = x[M,K] @ (q[K,N] * s[N]); grid over K row
  blocks of q (contiguous), full N per block, f32 VMEM accumulator.
- ``int8_matmul_t``: y[M,V] = x[M,D] @ (q[V,D] * s[V]).T; grid over V
  row blocks (contiguous), contracting the full D axis per block — the
  tied-embedding lm_head (embed is stored [V, D]) without ever
  materialising the transpose.

Single-device path (like ops/pallas_attention.py): under a TP mesh GSPMD
cannot partition a custom kernel, so the engine rejects the kernel
flags on a mesh and the mesh path runs the XLA matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fasttalk_tpu.ops.pallas_backend import resolve_interpret

# Per-block VMEM budget for the streamed q block (bytes, int8 elems).
# Double-buffered by the pipeline: 2x this resides in VMEM. Nothing in
# this file raises the compiler's scoped-VMEM limit (16 MiB by
# default), so 2 MiB blocks leave room for the accumulator/output
# while staying large enough to stream at HBM rate. Checked on a v5e
# with JAX 0.9.0 / libtpu 0.0.34: every llama3.2:1b shape compiles and
# runs at this size (scripts/check_kernels.py).
_BLOCK_BYTES = 2 * 1024 * 1024
# Working-set ceiling the supports() estimate checks against (blocks
# double-buffered + accumulator + output), a margin under that 16 MiB
# default; shapes that exceed it (the untied [4096, 128256] lm_head's
# full-N accumulator) take the XLA dequant path, and ops/quant.py
# records that they did.
_VMEM_BUDGET = 12 * 1024 * 1024


def _row_block(rows: int, cols: int) -> int | None:
    """Largest power-of-two row count dividing ``rows`` whose int8 block
    fits the VMEM budget. Minimum 128: the row count is the x-operand's
    LANE dimension in ``int8_matmul`` (and the output's in
    ``int8_matmul_t``), and Mosaic rejects sub-128 lane tiles
    ("Bad lhs type") — small-K weights fall back to the XLA dequant."""
    b = 1
    while b * 2 <= rows and rows % (b * 2) == 0 \
            and (b * 2) * cols <= _BLOCK_BYTES:
        b *= 2
    return b if rows % b == 0 and b >= 128 else None


def _mm_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, k_blocks: int,
               out_dtype):
    kb = pl.program_id(0)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    w = q_ref[:].astype(x_ref.dtype)  # int8 -> compute dtype, in VMEM
    acc_ref[:] += jax.lax.dot(x_ref[:], w,
                              preferred_element_type=jnp.float32)

    @pl.when(kb == k_blocks - 1)
    def _scale_out():
        scale = s_ref[0].astype(jnp.float32)[None, :]
        o_ref[:] = (acc_ref[:] * scale).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_matmul(x: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray,
                interpret: bool | None = None) -> jnp.ndarray:
    """x [M, K] @ dequant(q [K, N] int8, s [N]) -> [M, N] (x dtype)."""
    m, k = x.shape
    k2, n = q.shape
    assert k == k2 and s.shape == (n,)
    bk = _row_block(k, n)
    assert bk is not None, (k, n)
    interpret = resolve_interpret(interpret)
    k_blocks = k // bk

    return pl.pallas_call(
        functools.partial(_mm_kernel, k_blocks=k_blocks, out_dtype=x.dtype),
        grid=(k_blocks,),
        in_specs=[
            pl.BlockSpec((m, bk), lambda kb: (0, kb)),
            pl.BlockSpec((bk, n), lambda kb: (kb, 0)),  # contiguous rows
            pl.BlockSpec((1, n), lambda kb: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, n), lambda kb: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((m, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, q, s.reshape(1, n))


def _mm_t_kernel(x_ref, q_ref, s_ref, o_ref, *, out_dtype):
    w = q_ref[:].astype(x_ref.dtype)  # [bv, D] rows of the embedding
    acc = jax.lax.dot_general(
        x_ref[:], w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # [M, bv]
    o_ref[:] = (acc * s_ref[0].astype(jnp.float32)[None, :]).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_matmul_t(x: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray,
                  interpret: bool | None = None) -> jnp.ndarray:
    """x [M, D] @ dequant(q [V, D] int8, s [V]).T -> [M, V] (x dtype).

    The tied-embedding lm_head: q's rows are vocab entries (contiguous),
    contraction runs over the full D axis inside each block, so there is
    no accumulator carry between grid steps.
    """
    m, d = x.shape
    v, d2 = q.shape
    assert d == d2 and s.shape == (v,)
    bv = _row_block(v, d)
    assert bv is not None, (v, d)
    interpret = resolve_interpret(interpret)

    return pl.pallas_call(
        functools.partial(_mm_t_kernel, out_dtype=x.dtype),
        grid=(v // bv,),
        in_specs=[
            pl.BlockSpec((m, d), lambda vb: (0, 0)),
            pl.BlockSpec((bv, d), lambda vb: (vb, 0)),  # contiguous rows
            pl.BlockSpec((1, bv), lambda vb: (0, vb)),
        ],
        out_specs=pl.BlockSpec((m, bv), lambda vb: (0, vb)),
        out_shape=jax.ShapeDtypeStruct((m, v), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, q, s.reshape(1, v))


def supports(x_shape, q_shape, itemsize: int = 2) -> bool:
    """True when the kernel's blocking constraints hold for these shapes.
    ``itemsize``: activation/output element size (2 for bf16, 4 f32)."""
    if len(x_shape) != 2 or len(q_shape) != 2:
        return False
    m = x_shape[0]
    k, n = q_shape
    bk = _row_block(k, n)
    if n % 128 != 0 or bk is None:
        return False
    vmem = 2 * bk * n + 4 * m * n + itemsize * m * (n + k)
    return vmem <= _VMEM_BUDGET


def supports_t(x_shape, q_shape, itemsize: int = 2) -> bool:
    if len(x_shape) != 2 or len(q_shape) != 2:
        return False
    m = x_shape[0]
    v, d = q_shape
    bv = _row_block(v, d)
    if d % 128 != 0 or bv is None:
        return False
    vmem = 2 * bv * d + 2 * itemsize * m * bv + itemsize * m * d
    return vmem <= _VMEM_BUDGET


# ---------------------------------------------------------------------------
# Int4: nibble-packed weights (fasttalk_tpu/quantization/int4.py format),
# unpacked IN-REGISTER per tile so the packed uint8 bytes are what
# crosses HBM — a further 2x byte cut over the int8 kernel above.
# ---------------------------------------------------------------------------


def _row_block4(k: int, n: int, group: int) -> int | None:
    """Unpacked-row block size for the int4 kernel: a multiple of the
    scale group (so each block owns whole groups), >= 256 (the kernel
    reads x as even/odd halves, so HALF the block is the x operands'
    lane dimension — the 128-lane floor of _row_block, doubled),
    dividing ``k``, with the unpacked tile held to the int8 kernel's
    element budget (the packed bytes streamed are half that)."""
    best = None
    b = group
    while b <= k and k % b == 0:
        if b >= 256 and b * n <= _BLOCK_BYTES:
            best = b
        b *= 2
    return best


def _mm4_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *,
                k_blocks: int, half_group: int, out_dtype):
    kb = pl.program_id(0)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Unpack two's-complement nibbles: packed row j holds original row
    # 2j in the low nibble, 2j+1 in the high one. The bytes arrive as
    # int8 and widen to int32 first — Mosaic has no 8-bit vector
    # shifts ("failed to legalize arith.shli" on vector<..xi8>). The
    # widening sign-extends, so ``>> 4`` is already the signed high
    # nibble and ``(b << 28) >> 28`` sign-extends the low one.
    b = q_ref[:].astype(jnp.int32)  # [bk/2, n] packed pairs
    lo = ((b << 28) >> 28).astype(jnp.float32)
    hi = (b >> 4).astype(jnp.float32)
    # Both nibbles of a packed row belong to the same scale group (the
    # group size is even), so one expansion [gpb, n] -> [bk/2, n]
    # serves both halves: leading-dim-only broadcast+reshape, lane dim
    # untouched. Group scales vary along K, so the multiply must happen
    # per-tile inside the accumulation — it cannot factor out like the
    # int8 kernel's per-N scale.
    gpb, n = s_ref.shape[1], s_ref.shape[2]
    sexp = jnp.broadcast_to(
        s_ref[0].astype(jnp.float32)[:, None, :],
        (gpb, half_group, n)).reshape(gpb * half_group, n)
    # x arrives split into its even and odd columns (by the caller, on
    # the small operand): that replaces interleaving lo/hi back into
    # row order, which would be a sublane shuffle of the big one.
    dt = x_ref.dtype
    acc_ref[:] += (
        jax.lax.dot(x_ref[0], (lo * sexp).astype(dt),
                    preferred_element_type=jnp.float32)
        + jax.lax.dot(x_ref[1], (hi * sexp).astype(dt),
                      preferred_element_type=jnp.float32))

    @pl.when(kb == k_blocks - 1)
    def _out():
        o_ref[:] = acc_ref[:].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def int4_matmul(x: jnp.ndarray, q4: jnp.ndarray, s: jnp.ndarray,
                interpret: bool | None = None) -> jnp.ndarray:
    """x [M, K] @ dequant(q4 [K/2, N] packed int4, s [K/G, N]) -> [M, N]."""
    m, k = x.shape
    kp, n = q4.shape
    assert k == 2 * kp, (k, kp)
    groups = s.shape[0]
    assert s.shape == (groups, n) and k % groups == 0
    group = k // groups
    bk = _row_block4(k, n, group)
    assert bk is not None, (k, n, group)
    interpret = resolve_interpret(interpret)
    k_blocks = k // bk
    gpb = bk // group

    return pl.pallas_call(
        functools.partial(_mm4_kernel, k_blocks=k_blocks,
                          half_group=group // 2, out_dtype=x.dtype),
        grid=(k_blocks,),
        in_specs=[
            pl.BlockSpec((2, m, bk // 2), lambda kb: (0, 0, kb)),
            pl.BlockSpec((bk // 2, n), lambda kb: (kb, 0)),  # contiguous rows
            # Scales ride as [k_blocks, gpb, n] so a block's last two
            # dims are whole array dims: a (gpb, n) block of the 2-D
            # array has gpb < 8 sublanes, which the lowering rejects.
            pl.BlockSpec((1, gpb, n), lambda kb: (kb, 0, 0)),
        ],
        out_specs=pl.BlockSpec((m, n), lambda kb: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((m, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.moveaxis(x.reshape(m, kp, 2), 2, 0),  # [2, M, K/2] even | odd
      jax.lax.bitcast_convert_type(q4, jnp.int8),
      s.reshape(k_blocks, gpb, n))


def supports_q4(x_shape, q4_shape, s_shape, itemsize: int = 2) -> bool:
    """True when the int4 kernel's blocking constraints hold."""
    if len(x_shape) != 2 or len(q4_shape) != 2 or len(s_shape) != 2:
        return False
    m = x_shape[0]
    kp, n = q4_shape
    k = 2 * kp
    groups = s_shape[0]
    if s_shape[1] != n or groups <= 0 or k % groups:
        return False
    group = k // groups
    bk = _row_block4(k, n, group)
    if n % 128 != 0 or bk is None:
        return False
    # Packed block double-buffered (bk//2 * n * 2 = bk*n) + the f32
    # scale expansion (bk//2 * n * 4) + two dequantized half tiles +
    # accumulator + x + out.
    vmem = (3 + itemsize) * bk * n + 4 * m * n + itemsize * m * (n + k)
    return vmem <= _VMEM_BUDGET
