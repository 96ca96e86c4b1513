"""Mamba-2 (SSD) mixer — the attention-free assigned architecture.

Decode shares the paper's persistent-state structure: per head h a state
updated as S <- g*S + B x^T with output y = S^T C — i.e. the GDN
recurrence *without* the delta rule (`delta_rule=False` in the shared
kernels; see DESIGN.md §Arch-applicability).

The cached state is stored transposed, S^(h) in R^{d_head x d_state}
(``SSMState``): the 128-wide d_state axis is minor, so on a TPU the
state fills the 128 lanes of each tile.  Stored as (d_state, d_head)
its minor axis would be d_head = 64 and every tile half padding.  Decode
computes the step in the stored orientation (``ssd_decode_stored``);
prefill and training run the shared chunkwise core, which works in
(d_state, d_head), and swap the last two axes at the boundary.

Projections are kept separate (w_z / w_x / w_B / w_C / w_dt) so tensor
parallelism shards the per-head quantities (x, dt, heads) on the model axis
while the head-shared B/C (n_groups=1 — the SSM analogue of MQA) stay
replicated, Megatron-Mamba style.  Causal conv(4) applies depthwise to x,
B and C with separate filters (equivalent to the fused xBC conv).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import gdn as gdn_core
from repro.models import layers

# causal-conv width (fixed, as in Mamba-2); the mixer registry's cache_spec
# must describe carries of exactly this width
CONV_WIDTH = 4


class SSMState(NamedTuple):
    """Per-slot SSD cache.  ``S`` is (B, nheads, headdim, d_state): the
    transpose of the shared core's (d_k, d_v) = (d_state, headdim), so
    that d_state (128 at mamba2-1.3b's widths) is the minor axis.  Its
    bytes are those of the other orientation; only the axis order
    differs, so paging, gather/scatter, checkpoints and the disagg
    handoff, which all take their shapes from the mixer's
    ``cache_spec``, need nothing of their own."""
    S: jax.Array          # (B, nheads, headdim, d_state) fp32
    conv_x: jax.Array     # (B, conv_width-1, d_inner)
    conv_B: jax.Array     # (B, conv_width-1, d_state)
    conv_C: jax.Array     # (B, conv_width-1, d_state)


def init_ssm(key, d_model, d_inner, headdim, d_state,
             conv_width=CONV_WIDTH,
             dtype=jnp.float32):
    nheads = d_inner // headdim
    ks = jax.random.split(key, 9)
    s = d_model ** -0.5
    return {
        "w_z": (jax.random.normal(ks[0], (d_model, d_inner)) * s).astype(dtype),
        "w_x": (jax.random.normal(ks[1], (d_model, d_inner)) * s).astype(dtype),
        "w_B": (jax.random.normal(ks[2], (d_model, d_state)) * s).astype(dtype),
        "w_C": (jax.random.normal(ks[3], (d_model, d_state)) * s).astype(dtype),
        "w_dt": (jax.random.normal(ks[4], (d_model, nheads)) * s).astype(dtype),
        "conv_x": layers.init_conv1d(ks[5], d_inner, conv_width, dtype),
        "conv_B": layers.init_conv1d(ks[6], d_state, conv_width, dtype),
        "conv_C": layers.init_conv1d(ks[7], d_state, conv_width, dtype),
        "A_log": jnp.zeros((nheads,), jnp.float32),
        "dt_bias": jnp.full((nheads,), 0.5, jnp.float32),
        "D": jnp.ones((nheads,), jnp.float32),
        "norm": layers.init_rmsnorm(d_inner),
        "out_proj": (jax.random.normal(ks[8], (d_inner, d_model))
                     * (d_inner ** -0.5)).astype(dtype),
    }


_silu = layers.silu


def _ssd_terms(p, x_in, B_in, C_in, dt, headdim):
    """Post-conv activations -> kernel inputs. Shapes (..., nheads, hd) etc."""
    nheads = p["A_log"].shape[0]
    dt_s = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    log_g = -jnp.exp(p["A_log"]) * dt_s                   # (..., nheads)
    xh = x_in.reshape(*x_in.shape[:-1], nheads, headdim)
    v = (xh.astype(jnp.float32) * dt_s[..., None]).astype(x_in.dtype)
    return xh, v, log_g


def _out(p, y, z, xh, x_dtype):
    d_shape = (1,) * (y.ndim - 2) + (p["D"].shape[0], 1)
    y = y + p["D"].reshape(d_shape) * xh.astype(y.dtype)
    y = y.reshape(*y.shape[:-2], -1)
    y = layers.rmsnorm_fwd(p["norm"], y.astype(x_dtype))
    y = y * _silu(z)
    return layers.dot(y, p["out_proj"])


def _to_core(S):
    """Stored (..., headdim, d_state) <-> core (..., d_state, headdim)."""
    return jnp.swapaxes(S, -1, -2)


def ssd_decode_stored(C, B, v, S, g):
    """SSD decode step on the stored orientation.

    C, B: (batch, d_state); v: (batch, nheads, headdim);
    S: (batch, nheads, headdim, d_state); g: (batch, nheads).
    S' = g S + v B^T ; o = S' C.  Returns o (batch, nheads, headdim), S'.
    """
    S_new = g[..., None, None] * S + v[..., :, None] * B[:, None, None, :]
    return jnp.einsum("bhpn,bn->bhp", S_new, C), S_new


def ssm_train(p, x, *, d_inner, headdim, d_state, chunk=64):
    """Full-sequence SSD via the shared chunkwise path (delta_rule=False)."""
    B, T, _ = x.shape
    nheads = d_inner // headdim
    z = layers.dot(x, p["w_z"])
    xi = _silu(layers.conv1d_fwd(p["conv_x"], layers.dot(x, p["w_x"])))
    Bi = _silu(layers.conv1d_fwd(p["conv_B"], layers.dot(x, p["w_B"])))
    Ci = _silu(layers.conv1d_fwd(p["conv_C"], layers.dot(x, p["w_C"])))
    dt = layers.dot(x, p["w_dt"])
    xh, v, log_g = _ssd_terms(p, xi, Bi, Ci, dt, headdim)
    S0 = jnp.zeros((B, nheads, d_state, headdim), jnp.float32)
    O, _ = gdn_core.gdn_prefill(
        Ci[:, :, None, :].astype(jnp.float32),
        Bi[:, :, None, :].astype(jnp.float32),
        v.astype(jnp.float32), log_g, jnp.ones_like(log_g), S0,
        chunk=chunk, delta_rule=False)
    return _out(p, O.astype(x.dtype), z, xh, x.dtype)


def ssm_prefill(p, x, state: SSMState, *, d_inner, headdim, d_state,
                chunk=64, use_pallas=False, valid_len=None):
    B, T, _ = x.shape
    z = layers.dot(x, p["w_z"])
    xi, cx = layers.conv1d_prefill(p["conv_x"], layers.dot(x, p["w_x"]),
                           state.conv_x, valid_len)
    Bi, cB = layers.conv1d_prefill(p["conv_B"], layers.dot(x, p["w_B"]),
                           state.conv_B, valid_len)
    Ci, cC = layers.conv1d_prefill(p["conv_C"], layers.dot(x, p["w_C"]),
                           state.conv_C, valid_len)
    dt = layers.dot(x, p["w_dt"])
    xh, v, log_g = _ssd_terms(p, xi, Bi, Ci, dt, headdim)
    ones = jnp.ones_like(log_g)
    if use_pallas:
        from repro.kernels import ops
        O, S = ops.gdn_prefill(
            Ci[:, :, None, :], Bi[:, :, None, :], v, log_g,
            ones, _to_core(state.S), chunk=chunk, delta_rule=False,
            valid_len=valid_len)
        S = _to_core(S)
    else:
        Bk, vk, log_gk = Bi[:, :, None, :], v, log_g
        if valid_len is not None:
            from repro.models.gdn_layer import mask_ragged_inputs
            Bk, vk, log_gk, ones = mask_ragged_inputs(valid_len, Bk, vk,
                                                      log_gk, ones)
        O, S = gdn_core.gdn_prefill(
            Ci[:, :, None, :].astype(jnp.float32),
            Bk.astype(jnp.float32),
            vk.astype(jnp.float32), log_gk, ones,
            _to_core(state.S).astype(jnp.float32), chunk=chunk,
            delta_rule=False)
        S = _to_core(S).astype(state.S.dtype)
    out = _out(p, O.astype(x.dtype), z, xh, x.dtype)
    return out, SSMState(S=S, conv_x=cx.astype(state.conv_x.dtype),
                         conv_B=cB.astype(state.conv_B.dtype),
                         conv_C=cC.astype(state.conv_C.dtype))


def ssm_decode(p, x_t, state: SSMState, *, d_inner, headdim, d_state,
               use_pallas=False, head_block=8):
    """One-token decode on the stored (headdim, d_state) state
    (``ssd_decode_stored``), or through the fused kernel."""
    z = layers.dot(x_t, p["w_z"])
    xi, cx = layers.conv1d_decode(p["conv_x"], layers.dot(x_t, p["w_x"]),
                                  state.conv_x)
    Bi, cB = layers.conv1d_decode(p["conv_B"], layers.dot(x_t, p["w_B"]),
                                  state.conv_B)
    Ci, cC = layers.conv1d_decode(p["conv_C"], layers.dot(x_t, p["w_C"]),
                                  state.conv_C)
    xi, Bi, Ci = _silu(xi), _silu(Bi), _silu(Ci)
    dt = layers.dot(x_t, p["w_dt"])
    xh, v, log_g = _ssd_terms(p, xi, Bi, Ci, dt, headdim)
    g = jnp.exp(log_g)
    if use_pallas:
        from repro.kernels import ops
        o, S = ops.gdn_decode(Ci[:, None, :], Bi[:, None, :], v,
                              _to_core(state.S), g, jnp.ones_like(g),
                              head_block=head_block, delta_rule=False)
        S = _to_core(S)
    else:
        o, S = ssd_decode_stored(
            Ci.astype(jnp.float32), Bi.astype(jnp.float32),
            v.astype(jnp.float32), state.S.astype(jnp.float32), g)
        S = S.astype(state.S.dtype)
    out = _out(p, o.astype(x_t.dtype), z, xh, x_t.dtype)
    return out, SSMState(S=S, conv_x=cx, conv_B=cB, conv_C=cC)
