"""Qwen3-Next-80B-A3B's block — the ``qnext_gdn``, ``qnext_attn`` and
``qnext_moe`` mixer kinds — on the CPU at reduced widths, against the
benchmark's plain float32 reference (``benchmarks/onchip/harness``), on
seeded weights: each kind alone and the whole block through ragged
prefill chunks and decode, the whole model through ``serve.build`` and
the ``Router``, the decayed-read core, the expert share, the decode
counters, and the paper's ``gdn`` kind left bitwise as it was."""
import sys
from collections import namedtuple
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import gdn as gdn_core
from repro.launch import serve
from repro.models import layers, lm, moe
from repro.serving import scheduler as sched
from repro.serving.engine import DecodeEngine, Request
from repro.serving.executor import SwappedState

sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                       / "benchmarks" / "onchip"))
from harness import correct, reference  # noqa: E402
from harness.spec import load_config, load_mixer  # noqa: E402

ARCH = "qwen3-next-80b-a3b"
SEED = 2_000_000_011            # the program keeps seeds in int32
GOLDEN = Path(__file__).parent / "golden" / "gdn_bitwise.npz"
# float32 program against the float32 reference: they differ only in the
# order of sums (the chunkwise UT solve against the token loop, the conv,
# the combine of the experts), under 1e-5 on logits of order 1 for one
# kind alone: 1e-4 there.  Stacked, each GDN sublayer's normalizations
# (L2 of q and k after the conv, RMS of each 16-wide value head's output)
# amplify the rounding that reaches them, up to ten-fold per layer at
# these reduced widths (measured 2.6e-4 on the whole block): 1e-3 there.
# Both lie far below what a change of the mathematics moves (the paper's
# order of decay and delta, a missing gate, conv or norm: 1e-2 and more).
TOL_KIND, TOL_BLOCK = 1e-4, 1e-3


def _cfg(pattern=None):
    cfg = configs.get_arch(ARCH).reduced()
    if pattern is not None:
        cfg = cfg.replace(pattern=pattern, n_layers=2 * len(pattern))
    return cfg


def _layout(cfg):
    """The benchmark's layout of ``cfg`` (as the config file states it)."""
    return {
        "d_model": cfg.d_model, "n_layers": cfg.n_layers,
        "pattern": list(cfg.pattern), "vocab": cfg.vocab, "ffn": "none",
        "d_ff": 0, "norm_eps": cfg.norm_eps, "tie_embeddings": False,
        "act_dtype": cfg.act_dtype, "state_dtype": cfg.state_dtype,
        "mixers": {
            "qnext_gdn": {"k_heads": cfg.gdn_k_heads,
                          "v_heads": cfg.gdn_v_heads,
                          "head_dim": cfg.gdn_head_dim,
                          "conv_width": cfg.gdn_conv_width,
                          "norm_eps": cfg.norm_eps},
            "qnext_attn": {"heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
                           "head_dim": cfg.head_dim,
                           "rotary_dim": cfg.rotary_dim,
                           "rope_theta": cfg.rope_theta,
                           "norm_eps": cfg.norm_eps},
            "qnext_moe": {"experts": cfg.moe_experts,
                          "held": cfg.moe_experts_held, "first": 0,
                          "top_k": cfg.moe_top_k, "expert_width": cfg.d_ff,
                          "shared_width": cfg.moe_shared_d_ff}}}


def _reference_logits(cfg, tokens):
    """Every position's logits over the whole vocabulary."""
    B, T = tokens.shape
    lookup = np.broadcast_to(np.arange(cfg.vocab, dtype=np.int32),
                             (B, T, cfg.vocab))
    return reference.run(_layout(cfg), SEED, tokens, lookup)[2]


def _head(params, cfg, x):
    return lm._logits(params, cfg,
                      layers.rmsnorm_fwd(params["final_norm"], x,
                                         cfg.norm_eps))


def test_benchmark_config_states_the_registry_entry():
    """The benchmark's configuration file lays out the model the registry
    serves, at every published width and with the stated cut."""
    conf = load_config(ARCH)
    full = configs.get_arch(ARCH)
    assert conf["harness"]["arch"] == ARCH
    assert conf["harness"]["layout"] == _layout(full)
    assert conf["num_hidden_layers"] * 2 == full.n_layers
    assert conf["num_experts"] == full.moe_experts_held
    assert conf["router_outputs"] == full.moe_experts == 512
    assert (conf["num_experts_per_tok"], conf["moe_intermediate_size"],
            conf["shared_expert_intermediate_size"]) == (
        full.moe_top_k, full.d_ff, full.moe_shared_d_ff)
    assert conf["head_dim"] * conf["partial_rotary_factor"] \
        == full.rotary_dim


# ------------------------------------------------------- against the reference

@pytest.mark.parametrize("pattern,tol", [
    (("qnext_gdn",), TOL_KIND), (("qnext_attn",), TOL_KIND),
    (("qnext_moe",), TOL_KIND), (None, TOL_BLOCK)])
def test_program_matches_reference(pattern, tol):
    """Each kind alone, and the whole block (None): prompts of 21 and 18
    tokens in chunks of 8 whose last is ragged (a valid length per row),
    then 6 tokens decoded through the caches, give the logits the
    reference's full forward pass gives at every position."""
    cfg = _cfg(pattern)
    params = lm.init_lm(jax.random.PRNGKey(SEED), cfg)
    B, C, n_dec = 2, 8, 6
    lens = np.array([21, 18])
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(3),
                                           (B, 32), 1, cfg.vocab))
    want = _reference_logits(cfg, tokens[:, :int(lens.max()) + n_dec])

    caches = lm.init_caches(cfg, B, 64)
    got = np.zeros_like(want)
    for start in range(0, int(lens.max()), C):
        vl = np.clip(lens - start, 0, C).astype(np.int32)
        x, caches = lm.prefill_chunk(params, cfg, caches,
                                     tokens=jnp.asarray(
                                         tokens[:, start:start + C]),
                                     valid_len=jnp.asarray(vl))
        logits = np.asarray(_head(params, cfg, x))
        for b in range(B):
            got[b, start:start + vl[b]] = logits[b, :vl[b]]
    rows = np.arange(B)
    for j in range(n_dec):
        logits, caches = lm.decode_step(params, cfg,
                                        jnp.asarray(tokens[rows, lens + j]),
                                        caches)
        got[rows, lens + j] = np.asarray(logits)
    for b in range(B):
        n = lens[b] + n_dec
        np.testing.assert_allclose(got[b, :n], want[b, :n], rtol=tol,
                                   atol=tol)


Item = namedtuple("Item", "rid prompt")


def test_served_through_router_matches_reference():
    """The whole reduced model through ``serve.parse_args`` -> ``build``
    -> ``Router``, as the benchmark serves it: every greedy token served
    is the reference's argmax (float32 both sides: a gap above 1e-4
    would be a wrong token, not rounding), and each decode step's tick
    log carries the two MoE counters."""
    args = serve.parse_args(["--arch", ARCH, "--slots", "2", "--max-len",
                             "64", "--seed", str(SEED)])
    served = serve.build(args)
    cfg = served.cfg
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab, n,
                                               dtype=np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(11, 9), (23, 6), (5, 12)])]
    for r in reqs:
        served.router.submit(r)
    served.router.run_until_done()
    assert all(r.done and len(r.output) == r.max_new_tokens for r in reqs)

    sample = [(Item(r.rid, r.prompt), list(r.output)) for r in reqs]
    read = correct.gaps(_layout(cfg), SEED, sample, len(sample), 64)
    assert read["program"]["gap"] <= 1e-4, read

    ticks = served.engines[0].metrics()["tick_log"]
    assert ticks
    n_moe = cfg.layer_kinds.count("qnext_moe")
    for t in ticks:
        for live, a, h in zip(t["live"], t["moe_held_assignments"],
                              t["moe_held_experts_hit"]):
            assert 0 <= h <= min(a, n_moe * cfg.moe_experts_held)
            assert a <= live * cfg.moe_top_k * n_moe
    assert sum(sum(t["moe_held_assignments"]) for t in ticks) > 0


def test_pause_resume_carries_the_empty_moe_cache():
    """The MoE sublayer keeps no cache: its empty tree rides through the
    slot gather, the host image and the slot scatter beside the GDN
    state, conv carry and KV, and a stream paused mid-decode and resumed
    is bitwise the uninterrupted one."""
    cfg = _cfg()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)

    def serve_three(pause):
        eng = DecodeEngine(cfg, params, max_slots=2, max_len=64,
                           decode_block=2, prefill_chunk=8)
        reqs = [Request(rid=i, prompt=np.arange(1, 7 + 3 * i,
                                                dtype=np.int32),
                        max_new_tokens=8 + i) for i in range(3)]
        for r in reqs:
            eng.submit(r)
        if pause:
            while not (reqs[0].state == sched.ACTIVE
                       and len(reqs[0].output) >= 2):
                eng.step()
            eng.pause(0)
            assert isinstance(eng.swapped[0].state, SwappedState)
            eng.step()
            eng.step()
            eng.resume(0)
        eng.run_until_done()
        return [list(r.output) for r in reqs]

    assert serve_three(True) == serve_three(False)


# ------------------------------------------------------------- counters

def test_decode_counters_count_live_rows_only():
    """With every expert held each live token lands top_k assignments in
    each MoE layer; a row that is not live counts nothing; with half of
    them held, no more than that."""
    cut = _cfg()
    whole = cut.replace(moe_experts_held=cut.moe_experts)
    n_moe = cut.layer_kinds.count("qnext_moe")
    toks = jnp.array([3, 7, 11], jnp.int32)
    for cfg in (whole, cut):
        params = lm.init_lm(jax.random.PRNGKey(1), cfg)
        caches = lm.init_caches(cfg, 3, 16)
        live = jnp.array([True, False, True])
        _, _, c = lm.decode_step(params, cfg, toks, caches, live=live)
        a, h = int(c["moe_held_assignments"]), int(c["moe_held_experts_hit"])
        if cfg is whole:
            assert a == 2 * cfg.moe_top_k * n_moe
        else:
            assert 0 < a < 2 * cfg.moe_top_k * n_moe
        assert h <= min(a, n_moe * cfg.moe_experts_held)
        _, _, c = lm.decode_step(params, cfg, toks, caches,
                                 live=jnp.zeros(3, bool))
        assert int(c["moe_held_assignments"]) == 0
        assert int(c["moe_held_experts_hit"]) == 0
    assert lm.decode_counter_names(configs.get_arch("qwen3-next-gdn")) == ()


# ------------------------------------------------------------ expert share

def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of an expert-parallel stage, each holding a quarter of
    the experts (drawn from the same per-expert keys as a whole layer):
    their held parts, plus the shared expert once, give the uncut
    reference layer."""
    d, E, f, fs, k, chips = 32, 16, 24, 20, 4, 4
    n = E // chips
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 7, d))
    shares = [moe.init_held_moe(key, d, f, E, n, fs, first=c * n)
              for c in range(chips)]
    total = moe.shared_expert(shares[0], x)
    for c, p in enumerate(shares):
        w, _ = moe.held_routing(p, x, top_k=k, first=c * n)
        total = total + moe.held_experts(p, x, w)
    ref = load_mixer("qnext_moe")
    m = {"experts": E, "held": E, "first": 0, "top_k": k,
         "expert_width": f, "shared_width": fs}
    with jax.default_matmul_precision("highest"):
        want = ref.forward(ref.init(key, d, m, jnp.float32), x, m, "f32")
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- decayed-read core

def _seq(seed, T=48, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (T, d))
    k = jax.random.normal(ks[1], (T, d))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (T, d))
    log_g = -jax.nn.softplus(jax.random.normal(ks[3], (T,)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T,)))
    return q, k, v, log_g, beta


@pytest.mark.parametrize("seed", range(3))
def test_decayed_read_chunkwise_decode_and_loop_agree(seed):
    """S <- g S; r = S^T k; S <- S + k (beta (v - r))^T; o = S^T q / sqrt d:
    a plain loop of it, the scan of the fused decode step, the naive step
    and the chunkwise prefill give one output and state; and it is not
    the paper's order, which reads the undecayed state."""
    q, k, v, log_g, beta = _seq(seed)
    d = q.shape[-1]
    S0 = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (d, d))
    S, outs = S0, []
    for t in range(q.shape[0]):
        S = jnp.exp(log_g[t]) * S
        S = S + jnp.outer(k[t], beta[t] * (v[t] - S.T @ k[t]))
        outs.append(S.T @ q[t] / np.sqrt(d))
    O_loop = jnp.stack(outs)

    O_seq, S_seq = gdn_core.prefill_sequential(q, k, v, log_g, beta, S0,
                                               decayed_read=True)
    O_chk, S_chk = gdn_core.prefill_chunkwise(q, k, v, log_g, beta, S0,
                                              chunk=16, decayed_read=True)
    S_n = S0
    for t in range(q.shape[0]):
        o_n, S_n = gdn_core.decode_step_naive(q[t], k[t], v[t], S_n,
                                              jnp.exp(log_g[t]), beta[t],
                                              decayed_read=True)
    for O, Sf in ((O_seq, S_seq), (O_chk, S_chk)):
        np.testing.assert_allclose(O, O_loop, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(Sf, S, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(o_n, O_loop[-1], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(S_n, S, rtol=1e-4, atol=1e-4)
    O_paper, _ = gdn_core.prefill_sequential(q, k, v, log_g, beta, S0)
    assert float(jnp.max(jnp.abs(O_paper - O_loop))) > 1e-2


def test_decayed_read_batched_wrappers():
    """The batched decode and prefill (GVA, the layer's entry points)
    agree with each other under the decayed read."""
    B, Hk, Hv, d, T = 2, 2, 4, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    q = jax.random.normal(ks[0], (B, T, Hk, d))
    k = jax.random.normal(ks[1], (B, T, Hk, d))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, Hv, d))
    log_g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, Hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, Hv)))
    S = jnp.zeros((B, Hv, d, d))
    O, S_fin = gdn_core.gdn_prefill(q, k, v, log_g, beta, S, chunk=8,
                                    decayed_read=True)
    for t in range(T):
        o, S = gdn_core.gdn_decode(q[:, t], k[:, t], v[:, t], S,
                                   jnp.exp(log_g[:, t]), beta[:, t],
                                   decayed_read=True)
        np.testing.assert_allclose(o, O[:, t], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(S, S_fin, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------ the paper's kind

def _gdn_outputs():
    """The paper's ``gdn`` kind and core on fixed inputs: core prefill and
    decode (fused and naive), and the reduced qwen3-next-gdn model through
    prefill, a ragged chunk, a decode step and a 3-step tick."""
    out = {}
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    B, Hk, Hv, d, T = 2, 2, 4, 16, 32
    q = jax.random.normal(ks[0], (B, T, Hk, d))
    k = jax.random.normal(ks[1], (B, T, Hk, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, Hv, d))
    log_g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, Hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, Hv)))
    S0 = 0.1 * jax.random.normal(ks[5], (B, Hv, d, d))
    O, S = gdn_core.gdn_prefill(q, k, v, log_g, beta, S0, chunk=8)
    out["core/prefill_O"], out["core/prefill_S"] = O, S
    for fused in (True, False):
        o, S1 = gdn_core.gdn_decode(q[:, 0], k[:, 0], v[:, 0], S0,
                                    jnp.exp(log_g[:, 0]), beta[:, 0],
                                    fused=fused)
        out[f"core/decode_o_{fused}"], out[f"core/decode_S_{fused}"] = o, S1
    cfg = configs.get_arch("qwen3-next-gdn").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 13), 1, cfg.vocab)
    caches = lm.init_caches(cfg, 2, 32)
    lp, caches = lm.prefill(params, cfg, caches, tokens=toks[:, :8])
    x, caches = lm.prefill_chunk(params, cfg, caches, tokens=toks[:, 8:12],
                                 valid_len=jnp.array([3, 4], jnp.int32))
    ld, caches = lm.decode_step(params, cfg, toks[:, 12], caches)
    t, valid, last, caches, _ = lm.decode_steps(params, cfg, toks[:, 12],
                                                caches, 3)
    out.update({"lm/prefill": lp, "lm/chunk_x": x, "lm/decode": ld,
                "lm/steps": t})
    for i, leaf in enumerate(jax.tree.leaves(caches)):
        out[f"lm/cache{i}"] = leaf
    return {n: np.asarray(a) for n, a in out.items()}


def test_gdn_kind_bitwise_unchanged():
    """The decayed read and the Qwen3-Next kinds live beside the paper's
    layer in the shared core and model code; the ``gdn`` kind's outputs
    are bitwise those dumped before they came (tests/golden/README.md)."""
    golden = np.load(GOLDEN)
    got = _gdn_outputs()
    assert sorted(got) == sorted(golden.files)
    for name in golden.files:
        np.testing.assert_array_equal(got[name], golden[name], err_msg=name)
