"""Long-context decode with O(1) state — the paper's regime at scale.

Decodes with a mamba2 (SSD) model far past any window/cache size: the
recurrent state is a fixed (heads, d_head, d_state) tensor per layer no
matter how long the context grows — contrast with the full-attention archs
whose KV cache would grow linearly (and which therefore skip the 500k cell,
see DESIGN.md).  Also demonstrates state-consistency: decoding T tokens
step-by-step equals one chunkwise prefill over the same tokens.

    PYTHONPATH=src python examples/long_context_decode.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import lm


def main():
    cfg = configs.get_arch("mamba2-1.3b").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    B, T = 1, 48

    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T + 1), 0,
                                cfg.vocab)

    # (a) chunkwise prefill over T tokens, then one decode step
    caches = lm.init_caches(cfg, B, max_len=64)
    _, caches = lm.prefill(params, cfg, caches, tokens=tokens[:, :T])
    logits_a, _ = lm.decode_step(params, cfg, tokens[:, T], caches)

    # (b) pure decode: feed the same tokens one at a time
    caches_b = lm.init_caches(cfg, B, max_len=64)
    decode = jax.jit(lambda p, t, c: lm.decode_step(p, cfg, t, c),
                     donate_argnums=(2,))
    for t in range(T + 1):
        logits_b, caches_b = decode(params, tokens[:, t], caches_b)

    err = float(jnp.max(jnp.abs(logits_a - logits_b)))
    print(f"prefill+decode vs pure-decode max|dlogits| = {err:.2e}")
    assert err < 2e-2

    # state size is constant regardless of context length:
    state_bytes = sum(
        x.nbytes for x in jax.tree.leaves(caches_b))
    print(f"recurrent state/cache bytes: {state_bytes/1e3:.1f} KB "
          f"(constant in context length — the paper's enabling property)")


if __name__ == "__main__":
    main()
