"""jit'd public wrapper. ``interpret=True`` runs the kernel body in the Pallas
interpreter (any backend); the default compiles it, which only a TPU can."""
from __future__ import annotations

from .kernel import flash_attention as _kernel_call
from .ref import attention_ref


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bkv: int = 128, interpret: bool = False):
    """q: (B, H, S, D); k, v: (B, Hkv, S, D)."""
    return _kernel_call(q, k, v, bq=bq, bkv=bkv, causal=causal,
                        interpret=interpret)


__all__ = ["flash_attention", "attention_ref"]
