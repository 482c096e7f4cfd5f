"""Output-unit paging Pallas kernel — Sec. 4.3 / Fig. 6, TPU-native.

The paper's page = "all connections from layer i into ONE unit of layer i+1":
on the MCU only one page of weights is resident in RAM. The TPU analogue:
the grid walks the OUTPUT dimension; each grid step the BlockSpec stages
exactly one weight page (K × page) HBM→VMEM, while the input activation
(M × K) stays VMEM-resident (it is the small tensor, like the MCU input
vector). Peak weight residency = one page, independent of N — the same
RAM ∝ page-size guarantee as the paper, traded against grid latency.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

I8_MIN, I8_MAX = -128, 127


def _paged_kernel(x_ref, w_ref, bias_ref, resc_ref, wsum_ref, coff_ref,
                  zw_ref, out_ref, *, lo, hi):
    x = x_ref[...].astype(jnp.int32)                 # (M, K) resident
    w = w_ref[...].astype(jnp.int32)                 # (K, page) — this page only
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    sum_x = jnp.sum(x, axis=1, keepdims=True)
    inner = acc - zw_ref[...] * sum_x - wsum_ref[...] + coff_ref[...]
    y = bias_ref[...] + resc_ref[...] * inner.astype(jnp.float32)
    y = jnp.clip(y, lo, hi)
    out_ref[...] = jnp.clip(jnp.round(y), I8_MIN, I8_MAX).astype(jnp.int8)


@functools.partial(jax.jit,
                   static_argnames=("page", "lo", "hi", "interpret"))
def paged_qmatmul(x_q, w_q, bias_term, rescale, w_sum_zx, const_off, z_w,
                  *, page=128, lo=-jnp.inf, hi=jnp.inf, interpret=False):
    """x_q (M, K) int8, w_q (K, N) int8; N % page == 0. One weight page in
    VMEM per grid step."""
    m, k = x_q.shape
    _, n = w_q.shape
    assert n % page == 0, (n, page)

    def row(v, dtype):
        return jnp.broadcast_to(jnp.asarray(v, dtype).reshape(-1), (n,)) \
                  .reshape(1, n)

    consts = (row(bias_term, jnp.float32), row(rescale, jnp.float32),
              row(w_sum_zx, jnp.int32), row(const_off, jnp.int32),
              row(z_w, jnp.int32))
    const_spec = pl.BlockSpec((1, page), lambda j: (0, j))

    return pl.pallas_call(
        functools.partial(_paged_kernel, lo=lo, hi=hi),
        grid=(n // page,),
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),     # input stays resident
            pl.BlockSpec((k, page), lambda j: (0, j)),  # ONE page per step
            const_spec, const_spec, const_spec, const_spec, const_spec,
        ],
        out_specs=pl.BlockSpec((m, page), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        interpret=interpret,
        name="paged_matmul",
    )(x_q, w_q, *consts)
