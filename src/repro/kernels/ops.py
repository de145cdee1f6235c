"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels execute with ``interpret=True`` — the kernel
body runs in Python for correctness validation; on TPU the same code lowers
to Mosaic (``repro.kernels.interpret_mode``).  ``tiered_decode_attention``
composes the near-tier Pallas kernel with the far-tier XLA path and the
exact log-sum-exp merge — the two-tier read path of the TL-DRAM
adaptation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode as _interpret
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ssd_scan import ssd_chunk_scan
from repro.kernels.tiered_attention import near_decode_attention
from repro.kernels.tiered_gather import tiered_gather


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_kv: int = 128):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_kv=block_kv,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_t",))
def tiered_embedding_gather(near_table, near_slots, far_values,
                            block_t: int = 256):
    return tiered_gather(near_table, near_slots, far_values, block_t=block_t,
                         interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_h",))
def ssd_state_scan(states, decays, h0, block_h: int = 8):
    return ssd_chunk_scan(states, decays, h0, block_h=block_h,
                          interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_kv",))
def tiered_decode_attention(q, k_near, v_near, near_len,
                            k_far, v_far, far_len, block_kv: int = 128):
    """Two-tier decode attention (the TL-DRAM read path).

    q: (B,H,hd).  Near tier: contiguous (B,T_near,Hkv,hd) + live count —
    attended by the Pallas kernel (fast path).  Far tier: (B,T_far,Hkv,hd)
    + live count — attended by the XLA path (slow path).  Exact LSE merge.
    """
    near = near_decode_attention(q, k_near, v_near, near_len,
                                 block_kv=block_kv, interpret=_interpret())
    far = ref.decode_attention_stats_ref(q[:, None], k_far, v_far, far_len)
    return ref.merge_attention_stats([near, far])
