"""The SSD cache stores its state as (headdim, d_state) per head, the
transpose of the shared core's (d_state, headdim): decode steps it in
that orientation, prefill swaps the axes at the core's boundary.  These
tests hold both to the core's own SSD recurrence in float32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gdn as gdn_core
from repro.models import layers
from repro.models import ssm as ssm_layer

F32 = jnp.float32

# (batch, nheads, headdim, d_state): the reduced config's widths, and
# mamba2-1.3b's full head and state widths on 4 heads and 2 slots
WIDTHS = [pytest.param((2, 8, 16, 32), id="reduced"),
          pytest.param((2, 4, 64, 128), id="full-width")]


def _core_step(C, B, v, S_core, g):
    """core.gdn.ssd_decode_step per (slot, head) on the core's layout."""
    per_head = jax.vmap(gdn_core.ssd_decode_step,
                        in_axes=(None, None, 0, 0, 0))
    return jax.vmap(per_head)(C, B, v, S_core, g)


@pytest.mark.parametrize("widths", WIDTHS)
def test_decode_step_matches_core(widths):
    b, nh, hd, ds = widths
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    C = jax.random.normal(ks[0], (b, ds), F32)
    B = jax.random.normal(ks[1], (b, ds), F32)
    v = jax.random.normal(ks[2], (b, nh, hd), F32)
    S = jax.random.normal(ks[3], (b, nh, hd, ds), F32)
    g = jax.random.uniform(ks[4], (b, nh), F32, 0.5, 1.0)

    o, S_new = ssm_layer.ssd_decode_stored(C, B, v, S, g)
    o_ref, S_ref = _core_step(C, B, v, jnp.swapaxes(S, -1, -2), g)
    assert S_new.shape == S.shape and o.shape == (b, nh, hd)
    np.testing.assert_allclose(S_new, jnp.swapaxes(S_ref, -1, -2),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(o, o_ref, rtol=1e-5, atol=1e-5)


def _layer(widths, key):
    b, nh, hd, ds = widths
    d_model = 64
    p = ssm_layer.init_ssm(key, d_model, nh * hd, hd, ds)
    dims = dict(d_inner=nh * hd, headdim=hd, d_state=ds)
    return p, dims, d_model


def _zero_state(b, nh, hd, ds, d_inner):
    w = ssm_layer.CONV_WIDTH - 1
    return ssm_layer.SSMState(
        S=jnp.zeros((b, nh, hd, ds), F32),
        conv_x=jnp.zeros((b, w, d_inner), F32),
        conv_B=jnp.zeros((b, w, ds), F32),
        conv_C=jnp.zeros((b, w, ds), F32))


def _sequential_layer(p, x, *, d_inner, headdim, d_state):
    """The layer over a whole sequence with the token-by-token core
    (``prefill_sequential``) in place of the chunkwise one: the layer's
    output and the final state, in the core's (d_state, headdim)."""
    b, T, _ = x.shape
    nh = d_inner // headdim
    silu = ssm_layer._silu
    z = layers.dot(x, p["w_z"])
    xi = silu(layers.conv1d_fwd(p["conv_x"], layers.dot(x, p["w_x"])))
    Bi = silu(layers.conv1d_fwd(p["conv_B"], layers.dot(x, p["w_B"])))
    Ci = silu(layers.conv1d_fwd(p["conv_C"], layers.dot(x, p["w_C"])))
    dt = layers.dot(x, p["w_dt"])
    xh, v, log_g = ssm_layer._ssd_terms(p, xi, Bi, Ci, dt, headdim)
    seq = lambda q, k, vv, lg, S0: gdn_core.prefill_sequential(
        q, k, vv, lg, jnp.ones_like(lg), S0, delta_rule=False)
    per_head = jax.vmap(seq, in_axes=(None, None, 1, 1, 0), out_axes=(1, 0))
    O, S = jax.vmap(per_head)(Ci, Bi, v, log_g,
                              jnp.zeros((b, nh, d_state, headdim), F32))
    return ssm_layer._out(p, O, z, xh, x.dtype), S


@pytest.mark.parametrize("widths", WIDTHS)
def test_prefill_then_decode_matches_sequential(widths):
    """Scan prefill of T tokens in two calls (the second starts from the
    first's stored state), then k decode steps, against one sequential
    pass over the same T + k tokens: every output and the final state
    (stored transposed) agree."""
    b, nh, hd, ds = widths
    T, k = 16, 4
    kp, kx = jax.random.split(jax.random.PRNGKey(1))
    p, dims, d_model = _layer(widths, kp)
    x = jax.random.normal(kx, (b, T + k, d_model), F32)

    state = _zero_state(b, nh, hd, ds, dims["d_inner"])
    outs = []
    for lo in (0, T // 2):
        out, state = ssm_layer.ssm_prefill(p, x[:, lo:lo + T // 2], state,
                                           chunk=4, **dims)
        outs.append(out)
    for t in range(T, T + k):
        y, state = ssm_layer.ssm_decode(p, x[:, t], state, **dims)
        outs.append(y[:, None])
    got = jnp.concatenate(outs, axis=1)

    ref, S_ref = _sequential_layer(p, x, **dims)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state.S, jnp.swapaxes(S_ref, -1, -2),
                               rtol=1e-4, atol=1e-4)
