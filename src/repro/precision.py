"""The precision of f32 contractions, decided in one place.

A TPU runs an f32 dot at DEFAULT precision as one bf16 pass — a different,
approximate result — so f32 contractions ask for HIGHEST there.  The
Pallas kernels are TPU code (Mosaic, or interpret mode on the CPU test
path) and always ask.  XLA-level contractions ask only when they run on a
TPU: elsewhere DEFAULT already is f32, and the CPU keeps its default dot
kernels, whose results the serving layer's bit-identity contract is
written against.
"""
from __future__ import annotations

import jax

F32_DOT = jax.lax.Precision.HIGHEST


def xla_f32_dot():
    """`precision=` for an f32 contraction outside a Pallas kernel."""
    return F32_DOT if jax.default_backend() == "tpu" else None
