"""Gated DeltaNet (GDN) recurrence — the paper's core primitive.

Implements, in pure JAX:

  * gates (paper Eqs. 5-6):      g_t = exp(-sigma(alpha_t) * exp(A_log) * softplus(dt_bias))
                                 beta_t = sigma(b_t)
  * naive decode step (Alg. 1):  3 passes over the d_k x d_v state S
  * fused decode step (Alg. 2):  1 read + 1 write pass, via the identity
                                 S_t^T q = g * S_{t-1}^T q + (q^T k) * dv
  * sequential prefill:          lax.scan of the decode step over tokens (oracle)
  * chunkwise-parallel prefill:  gated UT/WY transform, log-space decay ratios
                                 (train/prefill path; O(T/C) sequential steps)

Two orders of decay and delta (``decayed_read``):

  * the paper's Algorithm 1 (default): the delta reads the undecayed state,
        r = S^T k;  S <- g S + k (beta (v - r))^T
  * the published Gated DeltaNet (arXiv:2412.06464; Qwen3-Next): decay
    first, then the delta against the decayed state,
        S <- g S;  r = S^T k;  S <- S + k (beta (v - r))^T
    i.e. the same step with r scaled by g.  Its gate is
    ``qnext_log_gate``: log g = -exp(A_log) softplus(a + dt_bias).

Both share o = S_t^T q (scaled); a mixer kind picks the order.

Shape conventions (single head):
  q, k          : (d_k,)
  v             : (d_v,)
  S             : (d_k, d_v)   -- state; retrieval r = S^T k  in (d_v,)
  g, beta       : scalars

Batched wrappers take (B, H, ...) leading axes. Grouped Value Attention (GVA):
h_v = R * h_k value heads; q/k head j serves v-heads j*R..(j+1)*R-1.

The mamba2 / SSD family is the delta_rule=False degenerate case (u_t = v_t,
no correction term), exposed via the same chunkwise/sequential entry points.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Gates (paper Eqs. 5-6)
# ---------------------------------------------------------------------------

def softplus(x):
    return jax.nn.softplus(x)


def log_gate(alpha, A_log, dt_bias):
    """log g_t = -sigma(alpha_t) * exp(A_log) * softplus(dt_bias).  <= 0 always."""
    return -jax.nn.sigmoid(alpha) * jnp.exp(A_log) * softplus(dt_bias)


def qnext_log_gate(a, A_log, dt_bias):
    """Qwen3-Next's gate: log g_t = -exp(A_log) * softplus(a_t + dt_bias)."""
    return -jnp.exp(A_log) * softplus(a + dt_bias)


def gates(alpha, b, A_log, dt_bias):
    """Paper Eqs. (5)-(6). Returns (g, beta), both in (0, 1)."""
    g = jnp.exp(log_gate(alpha, A_log, dt_bias))
    beta = jax.nn.sigmoid(b)
    return g, beta


# ---------------------------------------------------------------------------
# Single-head decode steps
# ---------------------------------------------------------------------------

def decode_step_naive(q, k, v, S, g, beta, *, scale=None,
                      decayed_read=False):
    """Alg. 1 — three logical passes over S (retrieval, update, output).
    ``decayed_read``: decay S first and retrieve from the decayed state."""
    d_k = q.shape[-1]
    scale = (1.0 / math.sqrt(d_k)) if scale is None else scale
    if decayed_read:
        S = g * S                      # decay first
        r = S.T @ k                    # pass 1 (read)
        S_new = S + jnp.outer(k, beta * (v - r))
        return scale * (S_new.T @ q), S_new
    r = S.T @ k                        # pass 1 (read)
    dv = beta * (v - r)                # delta correction
    S_new = g * S + jnp.outer(k, dv)   # pass 2 (read+write)
    o = scale * (S_new.T @ q)          # pass 3 (read)
    return o, S_new


def decode_step_fused(q, k, v, S, g, beta, *, scale=None,
                      decayed_read=False):
    """Alg. 2 — one read pass (computing r and o_hat together) + one write pass.

    The read pass stacks [k, q] into a single (2, d_k) @ (d_k, d_v) matmul:
    on TPU this is one MXU operation over a single traversal of S — the
    direct analogue of the paper's shared-read-pass datapath.  With
    ``decayed_read`` the retrieval is from g S, i.e. g (S^T k): the same
    passes, one more scaling.
    """
    d_k = q.shape[-1]
    scale = (1.0 / math.sqrt(d_k)) if scale is None else scale
    kq = jnp.stack([k, q])             # (2, d_k)
    rr = kq @ S                        # (2, d_v): rr[0] = S^T k, rr[1] = S^T q
    r, sq = rr[0], rr[1]
    if decayed_read:
        r = g * r
    o_hat = g * sq
    dv = beta * (v - r)
    alpha = q @ k                      # phase 1: dot product
    o = scale * (o_hat + alpha * dv)   # phase 4: output correction
    S_new = g * S + jnp.outer(k, dv)   # phase 5: single write pass
    return o, S_new


def ssd_decode_step(q, k, v, S, g, *, scale=None):
    """Mamba-2 / SSD decode: S_t = g*S + k v^T ; o = scale * S_t^T q.

    (GDN without the delta rule; shares the fused read/write structure.)
    """
    scale = 1.0 if scale is None else scale
    S_new = g * S + jnp.outer(k, v)
    o = scale * (S_new.T @ q)
    return o, S_new


# ---------------------------------------------------------------------------
# Sequential prefill (oracle) — scan the fused step over tokens
# ---------------------------------------------------------------------------

def prefill_sequential(q, k, v, log_g, beta, S0, *, scale=None,
                       delta_rule=True, decayed_read=False):
    """Token-by-token scan. q,k: (T, d_k); v: (T, d_v); log_g, beta: (T,).

    Returns O: (T, d_v), S_final: (d_k, d_v).
    """
    d_k = q.shape[-1]
    if scale is None:
        scale = (1.0 / math.sqrt(d_k)) if delta_rule else 1.0

    def step(S, inp):
        q_t, k_t, v_t, lg_t, b_t = inp
        g_t = jnp.exp(lg_t)
        if delta_rule:
            o, S_new = decode_step_fused(q_t, k_t, v_t, S, g_t, b_t,
                                         scale=scale,
                                         decayed_read=decayed_read)
        else:
            o, S_new = ssd_decode_step(q_t, k_t, v_t, S, g_t, scale=scale)
        return S_new, o

    S_final, O = jax.lax.scan(step, S0, (q, k, v, log_g, beta))
    return O, S_final


# ---------------------------------------------------------------------------
# Chunkwise-parallel prefill (gated UT transform)
# ---------------------------------------------------------------------------
#
# Within a chunk of length C with cumulative log-decay L_t = sum_{r<=t} log g_r:
#   u_t = beta_t (v_t - S_{t-1}^T k_t)
#   (I + A) U = beta ⊙ (V - gamma_prev ⊙ (K @ S0)),
#       A[t,s] = beta_t * exp(L_{t-1} - L_s) * (k_t . k_s),  s < t
#   O  = scale * (gamma ⊙ (Q @ S0) + M @ U),
#       M[t,s] = exp(L_t - L_s) * (q_t . k_s),               s <= t
#   S_C = exp(L_C) S0 + (exp(L_C - L) ⊙ K)^T @ U
#
# With the decayed read, u_t = beta_t (v_t - g_t S_{t-1}^T k_t): L_{t-1}
# becomes L_t in A and gamma_prev becomes gamma in the right-hand side.
#
# All decay ratios exp(L_a - L_b) have a >= b hence are <= 1: log-space is
# numerically safe for arbitrarily strong gating.

def _chunk_delta(q, k, v, log_g, beta, S0, scale, decayed_read=False):
    C, d_k = q.shape
    L = jnp.cumsum(log_g)                             # (C,)
    # the decay that the retrieval of token t sees
    L_prev = L if decayed_read else L - log_g         # L_t or L_{t-1}
    gamma = jnp.exp(L)                                # (C,)
    gamma_prev = jnp.exp(L_prev)

    kk = k @ k.T                                      # (C, C)
    decayA = jnp.exp(L_prev[:, None] - L[None, :])    # exp(L_prev,t - L_s)
    A = beta[:, None] * decayA * kk
    A = jnp.tril(A, k=-1)                             # strictly lower

    rhs = beta[:, None] * (v - gamma_prev[:, None] * (k @ S0))   # (C, d_v)
    U = jax.scipy.linalg.solve_triangular(
        jnp.eye(C, dtype=q.dtype) + A, rhs, lower=True)

    qk = q @ k.T
    decayM = jnp.exp(L[:, None] - L[None, :])
    M = jnp.tril(decayM * qk)                         # inclusive lower
    O = scale * (gamma[:, None] * (q @ S0) + M @ U)

    w = jnp.exp(L[-1] - L)                            # (C,)
    S_new = jnp.exp(L[-1]) * S0 + (w[:, None] * k).T @ U
    return O, S_new


def _chunk_ssd(q, k, v, log_g, S0, scale):
    C, d_k = q.shape
    L = jnp.cumsum(log_g)
    gamma = jnp.exp(L)
    qk = q @ k.T
    decayM = jnp.exp(L[:, None] - L[None, :])
    M = jnp.tril(decayM * qk)
    O = scale * (gamma[:, None] * (q @ S0) + M @ v)
    w = jnp.exp(L[-1] - L)
    S_new = jnp.exp(L[-1]) * S0 + (w[:, None] * k).T @ v
    return O, S_new


def prefill_chunkwise(q, k, v, log_g, beta, S0, *, chunk=64, scale=None,
                      delta_rule=True, decayed_read=False):
    """Chunk-parallel prefill. T must be a multiple of `chunk` (pad upstream).

    q,k: (T, d_k); v: (T, d_v); log_g, beta: (T,); S0: (d_k, d_v).
    """
    T, d_k = q.shape
    chunk = min(chunk, T)
    assert T % chunk == 0, f"T={T} not a multiple of chunk={chunk}"
    n = T // chunk
    if scale is None:
        scale = (1.0 / math.sqrt(d_k)) if delta_rule else 1.0

    qs = q.reshape(n, chunk, d_k)
    ks = k.reshape(n, chunk, d_k)
    vs = v.reshape(n, chunk, -1)
    lgs = log_g.reshape(n, chunk)
    bs = beta.reshape(n, chunk)

    def step(S, inp):
        qc, kc, vc, lgc, bc = inp
        if delta_rule:
            O, S_new = _chunk_delta(qc, kc, vc, lgc, bc, S, scale,
                                    decayed_read)
        else:
            O, S_new = _chunk_ssd(qc, kc, vc, lgc, S, scale)
        return S_new, O

    S_final, O = jax.lax.scan(step, S0, (qs, ks, vs, lgs, bs))
    return O.reshape(T, -1), S_final


# ---------------------------------------------------------------------------
# Batched multi-head wrappers (B, H, ...) with GVA support
# ---------------------------------------------------------------------------

def gva_expand(x, n_rep: int):
    """Repeat q/k heads to match v-heads: (B, Hk, ...) -> (B, Hk*R, ...)."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=1)


@partial(jax.jit, static_argnames=("fused", "scale", "delta_rule",
                                   "decayed_read"))
def gdn_decode(q, k, v, S, g, beta, *, fused=True, scale=None,
               delta_rule=True, decayed_read=False):
    """Batched GDN decode step.

    q, k : (B, Hk, d_k);  v: (B, Hv, d_v);  S: (B, Hv, d_k, d_v)
    g, beta: (B, Hv).  Hv must be a multiple of Hk (GVA ratio R = Hv // Hk).
    delta_rule=False gives the mamba2/SSD update (beta ignored);
    decayed_read the published GDN order (see the module docstring).
    Returns o: (B, Hv, d_v), S_new: (B, Hv, d_k, d_v).
    """
    B, Hk, d_k = q.shape
    Hv = v.shape[1]
    R = Hv // Hk
    qe, ke = gva_expand(q, R), gva_expand(k, R)
    if delta_rule:
        fn = decode_step_fused if fused else decode_step_naive
        fn = partial(fn, scale=scale, decayed_read=decayed_read)
        return jax.vmap(jax.vmap(fn))(qe, ke, v, S, g, beta)
    fn = partial(ssd_decode_step, scale=scale)
    return jax.vmap(jax.vmap(fn))(qe, ke, v, S, g)


@partial(jax.jit, static_argnames=("chunk", "scale", "delta_rule",
                                   "decayed_read"))
def gdn_prefill(q, k, v, log_g, beta, S0, *, chunk=64, scale=None,
                delta_rule=True, decayed_read=False):
    """Batched chunkwise prefill.

    q, k: (B, T, Hk, d_k); v: (B, T, Hv, d_v); log_g, beta: (B, T, Hv);
    S0: (B, Hv, d_k, d_v).  Returns O: (B, T, Hv, d_v), S: (B, Hv, d_k, d_v).
    """
    B, T, Hk, d_k = q.shape
    Hv = v.shape[2]
    R = Hv // Hk
    qe = gva_expand(q.transpose(0, 2, 1, 3), R)     # (B, Hv, T, d_k)
    ke = gva_expand(k.transpose(0, 2, 1, 3), R)
    vh = v.transpose(0, 2, 1, 3)                    # (B, Hv, T, d_v)
    lgh = log_g.transpose(0, 2, 1)                  # (B, Hv, T)
    bh = beta.transpose(0, 2, 1)

    fn = partial(prefill_chunkwise, chunk=chunk, scale=scale,
                 delta_rule=delta_rule, decayed_read=decayed_read)
    O, S = jax.vmap(jax.vmap(fn))(qe, ke, vh, lgh, bh, S0)
    return O.transpose(0, 2, 1, 3), S
