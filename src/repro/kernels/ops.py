"""Public jit'd kernel entry points with backend dispatch.

Each op resolves to (a) the Pallas TPU kernel on TPU backends,
(b) the Pallas kernel in interpret mode when explicitly requested
(CPU validation), or (c) the pure-jnp reference (XLA path) otherwise.

Select with ``repro.kernels.ops.set_backend("xla"|"pallas"|"pallas_interpret")``
or per-call via ``impl=``; the ``REPRO_KERNEL_BACKEND`` environment
variable seeds the initial backend (so CI can rerun whole suites on
``pallas_interpret`` without touching test code).
"""
from __future__ import annotations

import os

import jax

from . import ref
from . import reloc_codec as _rc
from .flash_attention import flash_attention as _flash_pallas
from .mlstm import mlstm_chunkwise as _mlstm_pallas
from .moe_dispatch import gather_rows as _gather_pallas
from .moe_dispatch import moe_combine as _combine_pallas
from .rg_lru import rg_lru as _rg_lru_pallas

__all__ = ["set_backend", "get_backend", "resolve_backend", "attention",
           "gather_rows", "moe_combine", "rg_lru_scan", "mlstm",
           "reloc_encode_pack", "reloc_pack_rows", "reloc_decode_rows"]

_VALID = ("auto", "xla", "xla_naive", "pallas", "pallas_interpret")
_BACKEND = os.environ.get("REPRO_KERNEL_BACKEND", "auto")
if _BACKEND not in _VALID:          # typo'd env var must fail loudly at
    raise ValueError(               # import, not as silent auto fallback
        f"REPRO_KERNEL_BACKEND={_BACKEND!r} not in {_VALID}")


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


def _resolve(impl: str | None) -> str:
    b = impl or _BACKEND
    if b == "auto":
        b = "pallas" if jax.default_backend() == "tpu" else "xla"
    return b


def resolve_backend(impl: str | None = None) -> str:
    """The backend a call would dispatch to right now (``auto``
    resolved) — what :class:`~repro.core.transport.DeviceTransport`
    consults once per window to pick the fused or composite codec path,
    and what lands in ``TransportStats.codec_backend``."""
    return _resolve(impl)


def attention(q, k, v, *, causal=True, window=None, softcap=0.0,
              sm_scale=None, impl: str | None = None, **block_kw):
    b = _resolve(impl)
    if b == "xla":
        return ref.flash_ref(q, k, v, causal=causal, window=window,
                             softcap=softcap, sm_scale=sm_scale)
    if b == "xla_naive":
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap, sm_scale=sm_scale)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         softcap=softcap, sm_scale=sm_scale,
                         interpret=(b == "pallas_interpret"), **block_kw)


def gather_rows(x, idx, *, impl: str | None = None):
    b = _resolve(impl)
    if b == "xla":
        return ref.gather_rows_ref(x, idx)
    return _gather_pallas(x, idx, interpret=(b == "pallas_interpret"))


def moe_combine(y, slots, weights, *, impl: str | None = None):
    b = _resolve(impl)
    if b == "xla":
        return ref.moe_combine_ref(y, slots, weights)
    return _combine_pallas(y, slots, weights,
                           interpret=(b == "pallas_interpret"))


def rg_lru_scan(x, a, h0=None, *, impl: str | None = None, **block_kw):
    b = _resolve(impl)
    if b == "xla":
        return ref.rg_lru_ref(x, a, h0)
    return _rg_lru_pallas(x, a, h0, interpret=(b == "pallas_interpret"),
                          **block_kw)


def reloc_encode_pack(mat, idx, widths, *, pairs, slots, width,
                      impl: str | None = None):
    """Encode+pack: collection chunk rows → the ``(pairs, chunks, 1,
    128)`` uint32 send buffer (word view, destination permutation and
    padding in one call)."""
    b = _resolve(impl)
    if b in ("xla", "xla_naive"):
        return ref.reloc_encode_pack_ref(mat, idx, widths, pairs=pairs,
                                         slots=slots, width=width)
    return _rc.encode_pack(mat, idx, widths, pairs=pairs, slots=slots,
                           width=width,
                           interpret=(b == "pallas_interpret"))


def reloc_pack_rows(words, offsets, widths, *, pairs, slots, width,
                    impl: str | None = None):
    """Pack pre-encoded word rows of a flat arena into the send buffer."""
    b = _resolve(impl)
    if b in ("xla", "xla_naive"):
        return ref.reloc_pack_rows_ref(words, offsets, widths,
                                       pairs=pairs, slots=slots,
                                       width=width)
    return _rc.pack_rows(words, offsets, widths, pairs=pairs,
                         slots=slots, width=width,
                         interpret=(b == "pallas_interpret"))


def reloc_decode_rows(rows, *, nbytes, dtype, impl: str | None = None):
    """Unpack+decode: delivered word rows → typed chunk rows (class
    padding trimmed, manifest dtype restored)."""
    b = _resolve(impl)
    if b in ("xla", "xla_naive"):
        return ref.reloc_decode_rows_ref(rows, nbytes=nbytes, dtype=dtype)
    return _rc.decode_rows(rows, nbytes=nbytes, dtype=dtype,
                           interpret=(b == "pallas_interpret"))


def mlstm(q, k, v, i_gate, f_gate, *, impl: str | None = None,
          return_state: bool = False, **block_kw):
    b = _resolve(impl)
    if b == "xla":
        h, state = ref.mlstm_ref(q, k, v, i_gate, f_gate)
    else:
        h, state = _mlstm_pallas(q, k, v, i_gate, f_gate,
                                 interpret=(b == "pallas_interpret"),
                                 **block_kw)
    return (h, state) if return_state else h
