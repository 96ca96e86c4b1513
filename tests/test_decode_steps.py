"""Device-resident decode loop: k fused steps == k single steps.

Pins the scan-batched tick (``lm.decode_steps``) to the single-step path
token-for-token and cache-bitwise, and the engine's ``decode_block`` to
the k=1 engine, so fusing the hot loop can never change what is served.
The tick updates its stacked caches in place (``decode_stacked``); one
arch per mixer kind pins that to each layer's own ``decode`` stepped
over unstacked caches.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import layers, lm
from repro.models.mixers import get_mixer
from repro.serving.engine import DecodeEngine, Request


@pytest.fixture(scope="module")
def gdn_model():
    cfg = configs.get_arch("qwen3-next-gdn").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_decode_steps_matches_single_steps_bitwise(gdn_model):
    """One k=4 scan == 4 decode_step calls: same tokens, bitwise caches."""
    cfg, params = gdn_model
    B, T, k = 2, 8, 4
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 1, cfg.vocab)

    caches = lm.init_caches(cfg, B, 32)
    logits, caches = lm.prefill(params, cfg, caches, tokens=tokens)
    first = jnp.argmax(logits, -1).astype(jnp.int32)

    # reference: k greedy single steps
    ref_caches, cur, ref_toks = caches, first, []
    for _ in range(k):
        logits, ref_caches = lm.decode_step(params, cfg, cur, ref_caches)
        cur = jnp.argmax(logits, -1).astype(jnp.int32)
        ref_toks.append(cur)

    # fused: one k-step scan (default greedy sampler)
    toks, valid, last, scan_caches, _ = lm.decode_steps(
        params, cfg, first, caches, k)

    assert bool(valid.all())
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.stack(ref_toks)))
    np.testing.assert_array_equal(np.asarray(last), np.asarray(ref_toks[-1]))
    for a, b in zip(jax.tree.leaves(scan_caches),
                    jax.tree.leaves(ref_caches)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_steps_masks_done_slots(gdn_model):
    """A slot whose done flag is set re-feeds its token: its emissions are
    invalid and its token stream is frozen."""
    cfg, params = gdn_model
    B = 2
    caches = lm.init_caches(cfg, B, 32)
    tokens = jnp.asarray([3, 5], jnp.int32)

    def sample_fn(st, logits):
        return jnp.argmax(logits, -1).astype(jnp.int32), st

    sampler = {"done": jnp.asarray([False, True])}
    toks, valid, last, _, _ = lm.decode_steps(
        params, cfg, tokens, caches, 3, sampler=sampler,
        sample_fn=sample_fn)
    valid = np.asarray(valid)
    toks = np.asarray(toks)
    assert valid[:, 0].all() and not valid[:, 1].any()
    assert (toks[:, 1] == 5).all()           # frozen
    assert int(last[1]) == 5


def _per_layer_steps(params, cfg, tokens, caches, k, count):
    """``k`` greedy decode steps in plain Python: every layer's own
    ``decode`` (``decode_counted`` over all slots where the kind counts)
    on its slice of the caches, then the stacks rebuilt.  Returns (tokens
    (k, B), caches, {counter: (k,) int32})."""
    groups = lm.build_groups(cfg)
    act = jnp.dtype(cfg.act_dtype)
    live = jnp.ones(tokens.shape, bool)
    # per group, per pattern position: the list of each layer's cache
    per = [[[jax.tree.map(lambda a, r=r: a[r], gc[i]) for r in range(reps)]
            for i in range(len(kinds))]
           for (kinds, reps), gc in zip(groups, caches)]
    names = lm.decode_counter_names(cfg) if count else ()
    toks, counts = [], {n: [] for n in names}
    for _ in range(k):
        x = layers.embed_fwd(params["embed"], tokens).astype(act)
        step = {n: jnp.zeros((), jnp.int32) for n in names}
        for g, ((kinds, reps), gp) in enumerate(zip(groups,
                                                    params["groups"])):
            for r in range(reps):
                for i, kind in enumerate(kinds):
                    lp = jax.tree.map(lambda a: a[r], gp[i])
                    mixer = get_mixer(kind)
                    h = layers.rmsnorm_fwd(lp["norm1"], x, cfg.norm_eps)
                    if names and mixer.decode_counters:
                        mix, per[g][i][r], c = mixer.decode_counted(
                            lp["mixer"], cfg, h, per[g][i][r], live)
                        step = {n: step[n] + c[n] for n in names}
                    else:
                        mix, per[g][i][r] = mixer.decode(
                            lp["mixer"], cfg, h, per[g][i][r])
                    x, _ = lm._ffn_fwd(cfg, lp, x + mix, decode=True)
        h = layers.rmsnorm_fwd(params["final_norm"], x, cfg.norm_eps)
        tokens = jnp.argmax(lm._logits(params, cfg, h),
                            -1).astype(jnp.int32)
        toks.append(tokens)
        for n in names:
            counts[n].append(step[n])
    stacked = [[jax.tree.map(lambda *a: jnp.stack(a), *layer_caches)
                for layer_caches in g] for g in per]
    return (jnp.stack(toks), stacked,
            {n: jnp.stack(v) for n, v in counts.items()})


# one arch per mixer kind; "swa" and recurrentgemma's swa get a window of
# 4 so the 8-token prompt and the 6 steps wrap the rolling slot
CARRY_ARCHS = {"attn": "yi-9b", "swa": "h2o-danube-1.8b",
               "gdn": "qwen3-next-gdn", "ssm": "mamba2-1.3b",
               "rglru": "recurrentgemma-2b", "qnext": "qwen3-next-80b-a3b"}


@pytest.mark.parametrize("kind", sorted(CARRY_ARCHS))
def test_decode_steps_in_place_matches_per_layer_decode(kind):
    """``decode_steps`` carries the stacked caches through its layer
    scan and writes each layer's update into them (K/V one row per
    slot): bitwise the tokens, caches and counters of each layer's
    per-layer ``decode`` over unstacked caches.  Two full periods and a
    partial one, so the layer index runs over repeats and groups."""
    cfg = configs.get_arch(CARRY_ARCHS[kind]).reduced()
    cfg = cfg.replace(n_layers=2 * len(cfg.pattern) + 1)
    if cfg.window:
        cfg = cfg.replace(window=4)
    B, T, k = 2, 8, 6
    count = bool(lm.decode_counter_names(cfg))
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
    logits, caches = lm.prefill(params, cfg, lm.init_caches(cfg, B, 32),
                                tokens=prompt)
    first = jnp.argmax(logits, -1).astype(jnp.int32)

    want_toks, want_caches, want_counts = jax.jit(
        _per_layer_steps, static_argnums=(1, 4, 5))(
        params, cfg, first, caches, k, count)
    out = jax.jit(lm.decode_steps, static_argnums=(1, 4),
                  static_argnames=("count",))(
        params, cfg, first, caches, k, count=count)
    toks, _, last, got_caches = out[:4]

    np.testing.assert_array_equal(np.asarray(toks), np.asarray(want_toks))
    np.testing.assert_array_equal(np.asarray(last),
                                  np.asarray(want_toks[-1]))
    assert (jax.tree.structure(got_caches)
            == jax.tree.structure(want_caches))
    for a, b in zip(jax.tree.leaves(got_caches),
                    jax.tree.leaves(want_caches)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if count:
        assert set(out[5]) == set(want_counts)
        for n in want_counts:
            np.testing.assert_array_equal(np.asarray(out[5][n]),
                                          np.asarray(want_counts[n]))


def _engine_outputs(cfg, params, k, *, stochastic=False, eos=None):
    eng = DecodeEngine(cfg, params, max_slots=2, max_len=64,
                       decode_block=k)
    reqs = [Request(rid=i, prompt=np.arange(1, 6 + i, dtype=np.int32),
                    max_new_tokens=5 + i,
                    temperature=0.8 if stochastic else 0.0,
                    top_k=10 if stochastic else 0,
                    top_p=0.9 if stochastic else 1.0,
                    eos_id=eos)
            for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return eng, [list(r.output) for r in reqs]


def test_engine_block_parity_greedy(gdn_model):
    """k-token ticks emit exactly what k single-token ticks emit."""
    cfg, params = gdn_model
    eng1, out1 = _engine_outputs(cfg, params, 1)
    eng4, out4 = _engine_outputs(cfg, params, 4)
    assert out1 == out4
    assert all(len(o) == 5 + i for i, o in enumerate(out1))
    assert eng4.ticks < eng1.ticks           # fewer host syncs


def test_engine_block_parity_stochastic(gdn_model):
    """Per-request device RNG streams make sampled outputs identical
    across decode_block too."""
    cfg, params = gdn_model
    _, out1 = _engine_outputs(cfg, params, 1, stochastic=True)
    _, out3 = _engine_outputs(cfg, params, 3, stochastic=True)
    assert out1 == out3


def test_engine_eos_mid_block(gdn_model):
    """EOS landing mid-block stops the request at the same token as k=1,
    and the freed slot is reused."""
    cfg, params = gdn_model
    # learn the greedy stream, then declare its 3rd token to be EOS
    _, ref = _engine_outputs(cfg, params, 1)
    eos = ref[0][2]
    _, out1 = _engine_outputs(cfg, params, 1, eos=eos)
    _, out4 = _engine_outputs(cfg, params, 4, eos=eos)
    assert out1 == out4
    for o in out1:
        assert eos not in o[:-1]             # nothing emitted past EOS


def test_engine_metrics(gdn_model):
    cfg, params = gdn_model
    eng, _ = _engine_outputs(cfg, params, 4)
    m = eng.metrics()
    assert m["requests"] == 4
    assert m["decode_block"] == 4
    assert m["tokens"] == sum(5 + i for i in range(4))
    assert m["decoded_tokens"] == m["tokens"] - 4   # admit emits 1 each
    assert m["decode_s"] > 0 and m["decode_us_per_token"] > 0
    assert m["mean_ttft_s"] > 0
    assert m["mean_latency_s"] >= m["mean_ttft_s"]
    assert m["mean_tokens_per_s"] > 0
    for r in eng._all:
        assert r.ttft_s is not None and r.ttft_s >= 0
        assert r.latency_s is not None and r.latency_s >= r.ttft_s
