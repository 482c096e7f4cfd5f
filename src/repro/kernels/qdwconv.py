"""Quantized DepthwiseConv2D Pallas kernel — Eq. (9), TPU-native.

MobileNet-style depthwise convolutions dominate the paper's person-detector
model. TPU adaptation: channels are the fast (lane) dimension, so the kernel
blocks over channels (bc lanes per grid step) and keeps the whole spatial
extent in VMEM (TinyML feature maps are tiny: 96×96×8 int8 = 72 KiB). The
kh×kw taps are a static unrolled loop of strided VMEM loads — the MCU's
sliding-window "view extraction" (Algorithm 1) becomes vectorized lane math.

The int8 block is widened once into an int32 VMEM scratch and every tap is
a (possibly strided) load from that scratch: Mosaic lowers strided loads
only for 32-bit data, and refuses a strided slice of an in-register value.
The scratch costs 4·H·W·bc bytes — 1.2 MiB at the person model's largest
block (50×50×128).

Input must be pre-padded (ops.qdwconv_folded handles SAME), kernel is VALID.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I8_MIN, I8_MAX = -128, 127


def _qdwconv_kernel(x_ref, w_ref, bias_ref, resc_ref, wsum_ref, coff_ref,
                    zw_ref, out_ref, x32_ref, *, kh, kw, stride, lo, hi,
                    c_true):
    sh, sw = stride
    cc = pl.program_id(1)
    _, oh, ow, bc = out_ref.shape
    x32_ref[...] = x_ref[...].astype(jnp.int32)   # (1, H, W, bc)
    w = w_ref[...].astype(jnp.int32)              # (kh, kw, bc)

    acc = jnp.zeros((1, oh, ow, bc), jnp.int32)
    sum_x = jnp.zeros((1, oh, ow, bc), jnp.int32)
    for i in range(kh):                       # static tap loop (Algorithm 1)
        for j in range(kw):
            sl = x32_ref[:, pl.ds(i, oh, stride=sh),
                         pl.ds(j, ow, stride=sw), :]   # (1, oh, ow, bc)
            acc = acc + sl * w[i, j]          # ΣΣ X W   per channel
            sum_x = sum_x + sl                # ΣΣ X     per channel

    inner = acc - zw_ref[...] * sum_x - wsum_ref[...] + coff_ref[...]
    y = bias_ref[...] + resc_ref[...] * inner.astype(jnp.float32)
    y = jnp.clip(y, lo, hi)
    q = jnp.clip(jnp.round(y), I8_MIN, I8_MAX).astype(jnp.int8)
    if c_true is not None:
        # Padded-layout contract: channel lanes >= c_true are written as
        # zero so downstream layers can consume the padded block unsliced.
        lane = jax.lax.broadcasted_iota(jnp.int32, q.shape, 3) + cc * bc
        q = jnp.where(lane < c_true, q, 0)
    out_ref[...] = q


@functools.partial(
    jax.jit, static_argnames=("stride", "out_hw", "bc", "lo", "hi", "c_true",
                              "interpret"))
def qdwconv(x_q, w_q, bias_term, rescale, w_sum_zx, const_off, z_w,
            *, stride, out_hw, bc=128, lo=-jnp.inf, hi=jnp.inf, c_true=None,
            interpret=False):
    """x_q (B, H, W, C) int8 pre-padded, w_q (kh, kw, C) int8, consts (C,).
    C % bc == 0 (ops wrapper pads channels). ``c_true``: when set, output
    lanes >= c_true are written as zero (padded-layout contract)."""
    b, H, W, c = x_q.shape
    kh, kw, _ = w_q.shape
    oh, ow = out_hw
    assert c % bc == 0, (c, bc)

    def row(v, dtype):
        return jnp.broadcast_to(jnp.asarray(v, dtype).reshape(-1), (c,)) \
                  .reshape(1, 1, 1, c)

    consts = (row(bias_term, jnp.float32), row(rescale, jnp.float32),
              row(w_sum_zx, jnp.int32), row(const_off, jnp.int32),
              row(z_w, jnp.int32))
    const_spec = pl.BlockSpec((1, 1, 1, bc), lambda n, cc: (0, 0, 0, cc))

    return pl.pallas_call(
        functools.partial(_qdwconv_kernel, kh=kh, kw=kw, stride=tuple(stride),
                          lo=lo, hi=hi, c_true=c_true),
        grid=(b, c // bc),
        in_specs=[
            pl.BlockSpec((1, H, W, bc), lambda n, cc: (n, 0, 0, cc)),
            pl.BlockSpec((kh, kw, bc), lambda n, cc: (0, 0, cc)),
            const_spec, const_spec, const_spec, const_spec, const_spec,
        ],
        out_specs=pl.BlockSpec((1, oh, ow, bc), lambda n, cc: (n, 0, 0, cc)),
        out_shape=jax.ShapeDtypeStruct((b, oh, ow, c), jnp.int8),
        scratch_shapes=[pltpu.VMEM((1, H, W, bc), jnp.int32)],
        interpret=interpret,
        name="qdwconv",
    )(x_q, w_q, *consts)
