"""Public jit'd entry points for the Pallas kernels.

Backend selection: the platform decides.  On a TPU the kernels run compiled
(interpret=False); on any other backend (the CPU test runs) they run in
interpret mode, which executes the same kernel body and BlockSpec pipeline
in Python for bit-faithful validation against ref.py.

The model code (src/repro/models) calls these through ``use_pallas`` config
switches; the multi-pod dry-run lowers the algebraically-identical pure-JAX
paths (see DESIGN.md §2 — XLA fuses the stacked read-pass matmul the same
way, and Pallas TPU kernels cannot be lowered for the CPU dry-run backend).
"""
from __future__ import annotations

import jax

from repro.kernels.gdn_decode import gdn_decode_pallas
from repro.kernels.gdn_prefill import gdn_prefill_pallas
from repro.kernels.attn_decode import attn_decode_pallas
from repro.kernels import ref


def interpret_mode() -> bool:
    """Interpret the kernels unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


def gdn_decode(q, k, v, S, g, beta, *, head_block=8, scale=None,
               delta_rule=True, interpret=None):
    """Fused persistent-state GDN decode step (paper Alg. 2)."""
    if interpret is None:
        interpret = interpret_mode()
    return gdn_decode_pallas(q, k, v, S, g, beta, head_block=head_block,
                             scale=scale, delta_rule=delta_rule,
                             interpret=interpret)


def gdn_prefill(q, k, v, log_g, beta, S0, *, chunk=64, scale=None,
                delta_rule=True, interpret=None, valid_len=None):
    """Chunkwise prefill, state resident in VMEM across the chunk grid.

    Batched head layout: q,k (B, T, Hk, d_k), v (B, T, Hv, d_v),
    log_g/beta (B, T, Hv), S0 (B, Hv, d_k, d_v).  GVA q/k sharing is done
    via the kernel's row indexing (q/k rows repeated per v-head pair).

    ``valid_len`` (optional, scalar or (B,) int32): ragged sequences padded
    to T — the kernel masks positions >= valid_len so the returned state
    and the valid output rows are exactly those of the unpadded sequence.
    """
    import jax.numpy as jnp
    if interpret is None:
        interpret = interpret_mode()
    B, T, Hk, d_k = q.shape
    Hv = v.shape[2]
    d_v = v.shape[-1]
    R = Hv // Hk
    # (B, T, H, d) -> (B*H, T, d); repeat q/k rows for GVA
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    if R > 1:
        qh = jnp.repeat(qh, R, axis=1)
        kh = jnp.repeat(kh, R, axis=1)
    qh = qh.reshape(B * Hv, T, d_k)
    kh = kh.reshape(B * Hv, T, d_k)
    vh = v.transpose(0, 2, 1, 3).reshape(B * Hv, T, d_v)
    lgh = log_g.transpose(0, 2, 1).reshape(B * Hv, T)
    bh = beta.transpose(0, 2, 1).reshape(B * Hv, T)
    S0h = S0.reshape(B * Hv, d_k, S0.shape[-1])
    vlh = None
    if valid_len is not None:
        vl = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (B,))
        vlh = jnp.repeat(vl, Hv, axis=0)               # (B * Hv,)
    O, S = gdn_prefill_pallas(qh, kh, vh, lgh, bh, S0h, vlh, chunk=chunk,
                              scale=scale, delta_rule=delta_rule,
                              interpret=interpret)
    O = O.reshape(B, Hv, T, d_v).transpose(0, 2, 1, 3)
    S = S.reshape(B, Hv, d_k, -1)
    return O, S


def attn_decode(q, k_cache, v_cache, length, *, block_t=256, scale=None,
                window=None, interpret=None):
    """Flash-decode GQA attention against a KV cache."""
    if interpret is None:
        interpret = interpret_mode()
    return attn_decode_pallas(q, k_cache, v_cache, length, block_t=block_t,
                              scale=scale, window=window, interpret=interpret)


__all__ = ["gdn_decode", "gdn_prefill", "attn_decode", "interpret_mode",
           "ref"]
