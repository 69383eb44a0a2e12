"""jit'd public wrapper. ``interpret=True`` runs the kernel body in the Pallas
interpreter (any backend); the default compiles it, which only a TPU can."""
from __future__ import annotations

from .kernel import paged_attention as _kernel_call
from .kernel import paged_attention_verify as _verify_call
from .ref import paged_attention_ref, paged_attention_verify_ref


def paged_attention(q, k_pages, v_pages, table, lengths, *,
                    interpret: bool = False):
    """q: (B, H, D); k_pages, v_pages: (P, page, Hkv, D); table: (B, maxp) i32;
    lengths: (B,) i32."""
    b, h, d = q.shape
    hk = k_pages.shape[2]
    out = _kernel_call(q.reshape(b, hk, h // hk, d), k_pages, v_pages,
                       table, lengths, interpret=interpret)
    return out.reshape(b, h, d)


def paged_attention_verify(q, k_pages, v_pages, table, pos, *,
                           interpret: bool = False):
    """Batched k-position verify step (self-speculative decoding).

    q: (B, Sq, H, D) — query row ``r`` sits at cache position ``pos + r`` and
    attends causally up to it; k_pages, v_pages: (P, page, Hkv, D); table:
    (B, maxp) i32; pos: (B,) i32. Returns (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    hk = k_pages.shape[2]
    g = h // hk
    # (B, Sq, Hkv, G, D) -> (B, Hkv, Sq, G, D) -> (B, Hkv, Sq*G, D): rows of
    # one kv head are (query, group) row-major, matching the kernel's r // G
    qk = q.reshape(b, sq, hk, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, hk, sq * g, d)
    out = _verify_call(qk, k_pages, v_pages, table, pos, sq=sq,
                       interpret=interpret)
    return out.reshape(b, hk, sq, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, sq, h, d)


__all__ = ["paged_attention", "paged_attention_ref",
           "paged_attention_verify", "paged_attention_verify_ref"]
