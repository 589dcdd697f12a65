"""Int8 dense kernel for serving-tier inference quantization.

The serving tier's quantized predict step (``serve.quant``) replaces every
calibrated ``nn.Dense`` with

    y = (q(x / s_x) · W_q) · (s_x ⊗ s_w) + b

where ``W_q`` is the weight matrix symmetric-quantized per OUTPUT channel at
registration time and ``s_x`` is the layer's per-(model, bucket) activation
scale collected from calibration traffic during ``warmup()``. The XLA
expression materializes the int8 activation tensor in HBM between the
quantize and the matmul; this kernel fuses quantize → int8×int8 MXU matmul
(int32 accumulate) → dequantize + bias into one pass, so the only HBM
traffic is fp32 activations in, int8 weights in (4× fewer weight bytes than
fp32 — the memory-bound serving win), fp32 activations out.

Both routes compute the same quantization arithmetic (same rounding, same
clip, same int32 accumulation — the int8 products are exact in either, so
they differ only by ~1-ulp dequant/bias FMA fusion); the kernel is an
execution strategy, not a numerics change, and the per-head error bounds
the serving tier certifies at calibration time hold for either route.
Static fallback (odd shapes, VMEM budget, no Pallas backend) takes the XLA
expression.

A/B: the serving quant path as a whole rides ``HYDRAGNN_SERVE_QUANT`` /
``Serving.quantize``; this module's ``kernel=`` argument (auto: TPU only,
``interpret=True`` testable anywhere) picks the execution route.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

_ROW_BLOCK = 8
_VMEM_LIMIT = 8 * 1024 * 1024


def quantize_weight(w: Array) -> tuple[Array, Array]:
    """Symmetric per-output-channel int8 weight quantization:
    ``(w_q int8 [K, N], s_w fp32 [N])`` with ``w ≈ w_q · s_w``."""
    absmax = jnp.max(jnp.abs(w), axis=0)
    s_w = jnp.maximum(absmax, 1e-12) / 127.0
    w_q = jnp.clip(jnp.round(w / s_w[None, :]), -127, 127).astype(jnp.int8)
    return w_q, s_w.astype(jnp.float32)


def _quantize_acts(x: Array, s_x: float) -> Array:
    return jnp.clip(
        jnp.round(x.astype(jnp.float32) / s_x), -127, 127
    ).astype(jnp.int8)


def reference_quant_dense(
    x: Array, w_q: Array, s_w: Array, s_x: float, bias: Array | None
) -> Array:
    """The XLA route — the single definition of the quantization arithmetic
    (the kernel below must match it exactly; tests pin this)."""
    x_q = _quantize_acts(x, s_x)
    acc = jax.lax.dot_general(
        x_q, w_q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    y = acc.astype(jnp.float32) * (s_x * s_w)[None, :]
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y


def _quant_kernel(x_ref, wq_ref, sw_ref, b_ref, o_ref, *, s_x: float):
    # the ONE quantization expression (shared with the XLA route): the
    # serving error certification relies on both routes rounding alike
    x_q = _quantize_acts(x_ref[...], s_x)
    acc = jax.lax.dot_general(
        x_q, wq_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    y = acc.astype(jnp.float32) * (s_x * sw_ref[0, :])[None, :]
    o_ref[...] = y + b_ref[0, :][None, :]


def quant_dense(
    x: Array,
    w_q: Array,
    s_w: Array,
    s_x: float,
    bias: Array | None = None,
    kernel: bool | None = None,
    interpret: bool | None = None,
    row_block: int | None = None,
) -> Array:
    """Quantized dense layer ``[M, K] × int8 [K, N] → fp32 [M, N]`` with the
    activation scale ``s_x`` baked as a compile-time constant (one executable
    per (model, bucket) — exactly the serving tier's AOT table shape).

    ``row_block`` is the kernel's only free geometry (rows per grid step,
    multiple of 8; default 8) — the axis the shared autotuner
    (``ops/autotune.py``) sweeps. Dense rows carry no layout contract, so
    any admissible block is exact; eligibility (VMEM, row count) is checked
    at the REQUESTED block. When ``row_block`` is None and
    ``HYDRAGNN_OPS_AUTOTUNE`` is set, a cached per-shape choice from the
    shared autotuner replaces the default (one dict read at trace time)."""
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if row_block is None:
        from .autotune import tuned_quant_row_block

        row_block = tuned_quant_row_block(x.shape[0], x.shape[1], w_q.shape[1])
    rb = _ROW_BLOCK if row_block is None else int(row_block)
    if rb < _ROW_BLOCK or rb % _ROW_BLOCK:
        raise ValueError(f"row_block must be a positive multiple of "
                         f"{_ROW_BLOCK}, got {rb}")
    s_x = float(s_x)
    m, k = x.shape
    n = w_q.shape[1]
    eligible = (
        kernel
        and m >= rb
        and (k * n + rb * (k + 2 * n)) * 4 <= _VMEM_LIMIT
        and jnp.issubdtype(x.dtype, jnp.floating)
    )
    if not eligible:
        return reference_quant_dense(x, w_q, s_w, s_x, bias)
    b = (bias if bias is not None else jnp.zeros((n,), jnp.float32))
    m_pad = -m % rb
    if m_pad:
        x = jnp.pad(x, ((0, m_pad), (0, 0)))
    g = x.shape[0] // rb
    out = pl.pallas_call(
        functools.partial(_quant_kernel, s_x=s_x),
        grid=(g,),
        in_specs=[
            pl.BlockSpec((rb, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),  # weights resident
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rb, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], n), jnp.float32),
        interpret=interpret,
    )(x, w_q, s_w.astype(jnp.float32).reshape(1, n),
      b.astype(jnp.float32).reshape(1, n))
    return out[:m] if m_pad else out


__all__ = ["quant_dense", "quantize_weight", "reference_quant_dense"]
