"""Public jit'd wrappers over the Pallas kernels.

`interpret` defaults to True off-TPU: the CPU test path, where the Pallas
kernels (authored for TPU) are validated in interpret mode against the
pure-jnp oracles in *_ref.py.  On a TPU the kernels always compile with
Mosaic — asking for interpret mode there is an error, never a silent
slow path."""
from __future__ import annotations

import jax

from .flash_attention import flash_attention as _flash
from .selective_scan import selective_scan as _selscan
from .segment_reduce import segment_reduce as _segred
from .segment_reduce import segment_sum as _segsum
from .tile_matmul import tile_matmul as _tilemm


def _interp() -> bool:
    return jax.default_backend() != "tpu"


def _mode(kw: dict) -> dict:
    kw.setdefault("interpret", _interp())
    if kw["interpret"] and not _interp():
        raise ValueError("Pallas interpret mode requested on a TPU backend")
    return kw


def segment_sum(ids, values, num_segments: int, **kw):
    return _segsum(ids, values, num_segments, **_mode(kw))


def segment_reduce(ids, values, num_segments: int, *, op: str = "+", **kw):
    return _segred(ids, values, num_segments, op=op, **_mode(kw))


def tile_matmul(a, b, tile_mask=None, **kw):
    return _tilemm(a, b, tile_mask=tile_mask, **_mode(kw))


def flash_attention(q, k, v, **kw):
    return _flash(q, k, v, **_mode(kw))


def selective_scan(a, bx, c, **kw):
    return _selscan(a, bx, c, **_mode(kw))
