"""Quantized int8 matmul Pallas kernel — the FullyConnected hot-spot (Eq. 3).

TPU adaptation of the paper's FC kernel: instead of the MCU's scalar MAC
loop, the contraction is blocked into MXU-aligned (128×128) VMEM tiles,
accumulated in int32, with the compile-time folded constants of Eq. (4)
applied once per output tile at the final K step. The input-dependent
``z_W · Σ_k X`` term is accumulated alongside the main product, so the kernel
remains a single pass over the data.

Grid: (M/bm, N/bn, K/bk), K innermost — each (i, j) output tile streams its
K-line of x/w tiles HBM→VMEM (this is the paper's paging idea applied to the
contraction dimension; see paged_matmul.py for the output-dimension paging of
Fig. 6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I8_MIN, I8_MAX = -128, 127


def _qmatmul_kernel(x_ref, w_ref, bias_ref, resc_ref, wsum_ref, coff_ref,
                    zw_ref, out_ref, acc_ref, sumx_ref, *, n_k, lo, hi,
                    n_true):
    k = pl.program_id(2)
    j = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        sumx_ref[...] = jnp.zeros_like(sumx_ref)

    x = x_ref[...]                            # (bm, bk) int8
    # int8 x int8 -> int32 is the MXU's integer contraction; widening the
    # operands first would ask for an int32 matmul, which the MXU lacks.
    acc_ref[...] += jax.lax.dot_general(
        x, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    sumx_ref[...] += jnp.sum(x.astype(jnp.int32), axis=1,
                             keepdims=True)   # (bm, 1)

    @pl.when(k == n_k - 1)
    def _finish():
        inner = (acc_ref[...]
                 - zw_ref[...] * sumx_ref[...]      # z_W Σ_k X  (input-dep.)
                 - wsum_ref[...]                    # z_X Σ_k W  (folded)
                 + coff_ref[...])                   # n z_X z_W  (folded)
        y = bias_ref[...] + resc_ref[...] * inner.astype(jnp.float32)
        y = jnp.clip(y, lo, hi)                     # fused activation
        q = jnp.clip(jnp.round(y), I8_MIN, I8_MAX).astype(jnp.int8)
        if n_true is not None:
            # Padded-layout contract: lanes >= n_true carry ZERO, so the next
            # layer's K-padding contributes nothing to its Σ X W or Σ X and
            # activations can stay tile-resident across layers.
            bm, bn = q.shape
            col = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) + j * bn
            q = jnp.where(col < n_true, q, 0)
        out_ref[...] = q


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "lo", "hi", "n_true", "interpret"))
def qmatmul(x_q, w_q, bias_term, rescale, w_sum_zx, const_off, z_w,
            *, bm=128, bn=128, bk=128, lo=-jnp.inf, hi=jnp.inf,
            n_true=None, interpret=False):
    """x_q (M, K) int8, w_q (K, N) int8, per-channel consts (N,) -> (M, N) int8.

    M, K, N must be multiples of the block sizes (ops.qmatmul_folded pads).
    ``n_true``: when set, output lanes >= n_true are written as zero — the
    padded-layout contract that lets chained layers skip the pad/slice pair.
    """
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (x_q.shape, w_q.shape, bm, bn, bk)
    n_k = k // bk

    def row(v, dtype):
        return jnp.broadcast_to(jnp.asarray(v, dtype).reshape(-1), (n,)) \
                  .reshape(1, n)

    consts = (row(bias_term, jnp.float32), row(rescale, jnp.float32),
              row(w_sum_zx, jnp.int32), row(const_off, jnp.int32),
              row(z_w, jnp.int32))
    const_spec = pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))

    return pl.pallas_call(
        functools.partial(_qmatmul_kernel, n_k=n_k, lo=lo, hi=hi,
                          n_true=n_true),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            const_spec, const_spec, const_spec, const_spec, const_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((bm, 1), jnp.int32),
        ],
        interpret=interpret,
        name="qmatmul",
    )(x_q, w_q, *consts)


# ---------------------------------------------------------------------------
# Generic float matmul kernel (used by the float FC path and dtype sweeps).
# ---------------------------------------------------------------------------

def _fmatmul_kernel(x_ref, w_ref, out_ref, acc_ref, *, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def fmatmul(x, w, *, bm=128, bn=128, bk=128, interpret=False):
    m, k = x.shape
    _, n = w.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(_fmatmul_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="fmatmul",
    )(x, w)
