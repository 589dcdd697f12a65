"""EXPERIMENTAL fp8 (e4m3 / e5m2) dense matmul — the step below bf16.

The MXU's native 8-bit float formats promise ~2× bf16 matmul throughput and
half the weight bytes, but fp8 training is NOT a validated precision here:
``Training.precision`` stops at bf16/fp16 (schema-enforced), and this module
is the contained experiment bench — the ``quant_matmul`` playbook re-run at
fp8:

    y = (q8(x / s_x) · q8(w / s_w)) · (s_x ⊗ s_w) + b

with ``q8`` a saturating cast to ``float8_e4m3fn`` (3 mantissa bits, max
448 — the forward/weight format) or ``float8_e5m2`` (2 mantissa bits, max
57344, fp16's exponent — the gradient format), weights scaled per OUTPUT
channel and activations per tensor. Like the int8 serving path, the
arithmetic has ONE definition (``reference_fp8_dense``); the Pallas kernel
is an execution strategy over the same expression, and
``certify_fp8_dense`` reports the measured error against the fp32 answer —
the same certify-then-serve contract ``serve.quant`` enforces at warm-up,
here exposed directly because there is no product path to arm yet.

A/B: ``HYDRAGNN_FP8_MATMUL`` picks the kernel-vs-XLA route (default: kernel
on TPU backends only; interpret=True testable anywhere). Nothing routes
through fp8 implicitly — callers opt in per matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

FP8_FORMATS = {
    "e4m3": jnp.float8_e4m3fn,
    "e5m2": jnp.float8_e5m2,
}
# largest finite value per format (the saturating-clip bound before cast)
FP8_MAX = {"e4m3": 448.0, "e5m2": 57344.0}

_ROW_BLOCK = 8
_VMEM_LIMIT = 8 * 1024 * 1024


def _flag_enabled() -> bool | None:
    from ..utils import flags

    return flags.get(flags.FP8_MATMUL)


def resolve_fp8_format(fmt: str):
    try:
        return FP8_FORMATS[fmt]
    except KeyError:
        raise ValueError(f"Unknown fp8 format {fmt!r}; one of "
                         f"{sorted(FP8_FORMATS)}")


def quantize_weight_fp8(w: Array, fmt: str = "e4m3") -> tuple[Array, Array]:
    """Per-output-channel fp8 weight quantization: ``(w_q fp8 [K, N],
    s_w fp32 [N])`` with ``w ≈ w_q · s_w`` — the ``quantize_weight`` shape
    at 8-bit float instead of int8 (scales map each column's absmax onto
    the format's finite range)."""
    dtype = resolve_fp8_format(fmt)
    absmax = jnp.max(jnp.abs(w), axis=0)
    s_w = jnp.maximum(absmax, 1e-12) / FP8_MAX[fmt]
    w_q = _quantize_fp8(w / s_w[None, :], fmt, dtype)
    return w_q, s_w.astype(jnp.float32)


def activation_scale_fp8(x: Array, fmt: str = "e4m3") -> Array:
    """Per-tensor activation scale (absmax onto the format range) — traced,
    so experiments can run without a calibration pass; an AOT deployment
    would bake a calibrated float like the int8 serving tier."""
    return jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / FP8_MAX[fmt]


def _quantize_fp8(x: Array, fmt: str, dtype) -> Array:
    # clip BEFORE the cast: e5m2 has inf, and an over-range cast would
    # manufacture it; e4m3fn saturates anyway, so the clip only pins the
    # two formats to the same (saturating) convention
    bound = FP8_MAX[fmt]
    return jnp.clip(x.astype(jnp.float32), -bound, bound).astype(dtype)


def reference_fp8_dense(
    x: Array, w_q: Array, s_w: Array, s_x, bias: Array | None,
    fmt: str = "e4m3",
) -> Array:
    """The XLA route — the single definition of the fp8 arithmetic (the
    kernel must match it exactly; tests pin this)."""
    dtype = resolve_fp8_format(fmt)
    x_q = _quantize_fp8(x / s_x, fmt, dtype)
    acc = jax.lax.dot_general(
        x_q, w_q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y = acc * (jnp.asarray(s_x, jnp.float32) * s_w)[None, :]
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y


def _fp8_kernel(x_ref, wq_ref, sw_ref, sx_ref, b_ref, o_ref, *, fmt: str):
    dtype = FP8_FORMATS[fmt]
    s_x = sx_ref[0, 0]
    x_q = _quantize_fp8(x_ref[...] / s_x, fmt, dtype)
    acc = jax.lax.dot_general(
        x_q, wq_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y = acc * (s_x * sw_ref[0, :])[None, :]
    o_ref[...] = y + b_ref[0, :][None, :]


def fp8_dense(
    x: Array,
    w: Array,
    bias: Array | None = None,
    fmt: str = "e4m3",
    s_x=None,
    kernel: bool | None = None,
    interpret: bool | None = None,
) -> Array:
    """Experimental fp8 dense layer ``[M, K] × [K, N] → fp32 [M, N]``:
    quantize activations (per-tensor) and weights (per-output-channel) to
    ``fmt``, matmul with fp32 accumulation, dequantize + bias. ``s_x`` may
    be a pre-calibrated float; default derives it from ``x`` in-program.
    Route: ``HYDRAGNN_FP8_MATMUL`` > backend default (kernel on TPU only);
    both routes compute the identical expression."""
    resolve_fp8_format(fmt)
    if kernel is None:
        flag = _flag_enabled()
        kernel = flag if flag is not None else jax.default_backend() == "tpu"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    w_q, s_w = quantize_weight_fp8(w, fmt)
    if s_x is None:
        s_x = activation_scale_fp8(x, fmt)
    m, k = x.shape
    n = w_q.shape[1]
    eligible = (
        kernel
        and m >= _ROW_BLOCK
        and (k * n + _ROW_BLOCK * (k + 2 * n)) * 4 <= _VMEM_LIMIT
        and jnp.issubdtype(x.dtype, jnp.floating)
    )
    if not eligible:
        return reference_fp8_dense(x, w_q, s_w, s_x, bias, fmt)
    b = (bias if bias is not None else jnp.zeros((n,), jnp.float32))
    m_pad = -m % _ROW_BLOCK
    if m_pad:
        x = jnp.pad(x, ((0, m_pad), (0, 0)))
    g = x.shape[0] // _ROW_BLOCK
    out = pl.pallas_call(
        functools.partial(_fp8_kernel, fmt=fmt),
        grid=(g,),
        in_specs=[
            pl.BlockSpec((_ROW_BLOCK, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),  # weights resident
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_ROW_BLOCK, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], n), jnp.float32),
        interpret=interpret,
    )(x.astype(jnp.float32), w_q, s_w.reshape(1, n),
      jnp.asarray(s_x, jnp.float32).reshape(1, 1),
      b.astype(jnp.float32).reshape(1, n))
    return out[:m] if m_pad else out


def certify_fp8_dense(
    x: Array, w: Array, bias: Array | None = None, fmt: str = "e4m3",
) -> dict:
    """Measured error of the fp8 expression against the fp32 matmul on this
    exact input — the serving tier's certify-before-serve discipline applied
    to the experiment: callers get numbers, not vibes. Returns max-abs and
    relative-Frobenius error plus the format's structural parameters."""
    w_q, s_w = quantize_weight_fp8(w, fmt)
    s_x = activation_scale_fp8(x, fmt)
    got = reference_fp8_dense(x, w_q, s_w, s_x, bias, fmt)
    want = x.astype(jnp.float32) @ w.astype(jnp.float32)
    if bias is not None:
        want = want + bias.astype(jnp.float32)
    diff = got - want
    denom = jnp.maximum(jnp.linalg.norm(want), 1e-12)
    return {
        "format": fmt,
        "max_abs_err": float(jnp.max(jnp.abs(diff))),
        "rel_fro_err": float(jnp.linalg.norm(diff) / denom),
        "mantissa_bits": 3 if fmt == "e4m3" else 2,
        "max_finite": FP8_MAX[fmt],
    }


__all__ = [
    "FP8_FORMATS",
    "FP8_MAX",
    "activation_scale_fp8",
    "certify_fp8_dense",
    "fp8_dense",
    "quantize_weight_fp8",
    "reference_fp8_dense",
    "resolve_fp8_format",
]
