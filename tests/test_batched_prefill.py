"""Batched multi-prompt prefill: one fixed-shape varlen program per tick.

The batched packer fuses every staged prompt into ONE
(staging_depth, _MAX_SCAN_CHUNKS, prefill_chunk) scan + ONE admit
program per dispatch (rows past a prompt's end are valid_len = 0
bitwise no-ops) and admits every finished row through ONE multi-row
scatter.  Every guarantee that fusion rests on is pinned here:

  * kernel parity — interpret-mode Pallas ``gdn_prefill`` with per-row
    *different* valid_lens (including a valid = 0 placeholder row)
    equals the row-by-row sequential oracle, and the placeholder row's
    state is untouched;
  * engine parity — batched token streams are bitwise identical to the
    per-prompt (``prefill_batching=False``) baseline for every mixer
    kind, greedy and stochastic, overlapped and serialized, across
    mixed ragged prompt lengths, ring depths and packer budgets
    (``admit_rows`` folds the same (seed, rid) keys as ``admit_row``,
    so draw streams are batching-invariant);
  * O(1) dispatch shapes — one engine serving every awkward length
    compiles ≤ 2 batched prefill programs (vs ≤ 5 per-prompt);
  * batch-admit semantics — rows admitted by one dispatch share ONE
    device sync and stamp the SAME ``t_first``; finished rows scatter
    in ONE multi-row dispatch;
  * fairness — strict oldest-first packing: a long staged prompt
    drains at full rate no matter how many short prompts arrive behind
    it (its dispatch count is bounded by its own chunk count);
  * gates — MoE FFNs and mixer kinds without per-row masks fall back
    to per-prompt staging (silently on auto, loudly when forced);
  * mesh — data-sharded batched serving stays bitwise; the
    head-sharded (4, 2) topology completes (subprocess, 8 virtual
    devices).

The CI kernel-path job re-runs this module with REPRO_PALLAS_SERVING=1
so the batched rows drive the Pallas prefill kernels (interpret mode).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import gdn as gdn_core
from repro.models import lm
from repro.serving.engine import DecodeEngine, Request

ARCHS = {
    "gdn": "qwen3-next-gdn",
    "ssm": "mamba2-1.3b",
    "rglru": "recurrentgemma-2b",
    "attn": "yi-9b",
    "swa": "h2o-danube-1.8b",
}


def _arch_cfg(name):
    cfg = configs.get_arch(name).reduced()
    if os.environ.get("REPRO_PALLAS_SERVING") == "1":
        cfg = cfg.replace(use_pallas_serving=True)
    return cfg


@pytest.fixture(scope="module")
def gdn_model():
    cfg = _arch_cfg(ARCHS["gdn"])
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ------------------------------------------------------- kernel parity

@pytest.mark.parametrize("delta_rule", [True, False], ids=["gdn", "ssd"])
def test_gdn_prefill_kernel_multirow_ragged(delta_rule):
    """Per-row DIFFERENT valid_lens — the exact operand the batched
    staging rows feed the kernel — match the row-by-row sequential
    oracle, and a valid = 0 placeholder row leaves its state bitwise
    untouched (the no-op guarantee the fixed-shape dispatch rests
    on)."""
    from repro.kernels.gdn_prefill import gdn_prefill_pallas
    rng = np.random.default_rng(7)
    BH, T, dk, dv, C = 4, 16, 8, 8, 4
    valids = np.array([3, 16, 0, 11], np.int32)     # ragged + placeholder
    q = jnp.asarray(rng.normal(size=(BH, T, dk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(BH, T, dk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(BH, T, dv)), jnp.float32)
    lg = jnp.asarray(-np.abs(rng.normal(size=(BH, T))), jnp.float32)
    b = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(BH, T)), jnp.float32))
    S0 = jnp.asarray(rng.normal(size=(BH, dk, dv)), jnp.float32)

    O, S = gdn_prefill_pallas(q, k, v, lg, b, S0, jnp.asarray(valids),
                              chunk=C, delta_rule=delta_rule,
                              interpret=True)
    for h, valid in enumerate(valids):
        if valid == 0:
            np.testing.assert_array_equal(np.asarray(S[h]),
                                          np.asarray(S0[h]))
            continue
        Oref, Sref = gdn_core.prefill_sequential(
            q[h, :valid], k[h, :valid], v[h, :valid], lg[h, :valid],
            b[h, :valid], S0[h], delta_rule=delta_rule)
        np.testing.assert_allclose(np.asarray(O[h, :valid]),
                                   np.asarray(Oref), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(S[h]), np.asarray(Sref),
                                   rtol=2e-5, atol=2e-5)


# ------------------------------------------------------- engine parity

# mixed ragged lengths with prefill_chunk=8: tail-only (6), scan+tail
# (17), exact chunk (8), multi-scan (26), single token (1), mid (13)
_LENS = (6, 17, 8, 26, 1, 13)


def _serve(cfg, params, *, batching, overlap=True, stochastic=False,
           depth=3, budget=None, slots=2):
    eng = DecodeEngine(cfg, params, max_slots=slots, max_len=64,
                       decode_block=4, overlap=overlap, prefill_chunk=8,
                       staging_depth=depth, prefill_batching=batching,
                       prefill_budget=budget)
    reqs = [Request(rid=i, prompt=np.arange(1, L + 1, dtype=np.int32),
                    max_new_tokens=3 + i,
                    temperature=0.8 if stochastic else 0.0,
                    top_k=10 if stochastic else 0,
                    top_p=0.9 if stochastic else 1.0)
            for i, L in enumerate(_LENS)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return eng, [list(r.output) for r in reqs]


@pytest.mark.parametrize("kind", sorted(ARCHS) + ["gdn_naive"])
def test_batched_streams_match_per_prompt(kind):
    """The tentpole guarantee: fusing all staged prompts into one
    fixed-shape program per dispatch never changes a token — batched
    streams are bitwise the per-prompt baseline's for every mixer kind,
    greedy AND stochastic."""
    arch = ARCHS.get(kind, ARCHS["gdn"])
    cfg = _arch_cfg(arch)
    if kind == "gdn_naive":
        cfg = cfg.replace(pattern=tuple(
            "gdn_naive" if k == "gdn" else k for k in cfg.pattern))
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    e_per, s_per = _serve(cfg, params, batching=False)
    e_bat, s_bat = _serve(cfg, params, batching=None)   # auto -> on
    assert not e_per.prefill_batching and e_bat.prefill_batching
    assert s_bat == s_per
    _, st_per = _serve(cfg, params, batching=False, stochastic=True)
    _, st_bat = _serve(cfg, params, batching=None, stochastic=True)
    assert st_bat == st_per


def test_batched_parity_across_knobs(gdn_model):
    """Ring depth, packer budget and overlap are pure scheduling knobs
    of the batched path: streams equal the serialized per-prompt
    baseline under every combination."""
    cfg, params = gdn_model
    _, base = _serve(cfg, params, batching=False, overlap=False)
    for kw in ({"overlap": False}, {"depth": 1}, {"depth": 4},
               {"budget": 8}, {"budget": 24}, {"slots": 1}):
        _, out = _serve(cfg, params, batching=True, **kw)
        assert out == base, f"batched diverged under {kw}"
    _, st_base = _serve(cfg, params, batching=False, overlap=False,
                        stochastic=True)
    _, st_bud = _serve(cfg, params, batching=True, budget=8,
                       stochastic=True)
    assert st_bud == st_base


def test_batched_compile_cache_o1(gdn_model):
    """One engine serving every awkward prompt length compiles at most
    2 batched prefill programs (one fixed-shape scan + one admit) — the
    fixed five-phase iteration regardless of occupancy, tighter than
    the per-prompt masked planner's ≤ 5."""
    cfg, params = gdn_model
    eng = DecodeEngine(cfg, params, max_slots=1, max_len=64,
                       decode_block=1, prefill_chunk=8)
    assert eng.prefill_batching
    for rid, T in enumerate((1, 7, 8, 9, 23, 40, 41, 57)):
        eng.submit(Request(rid=rid, prompt=np.arange(1, T + 1,
                                                     dtype=np.int32),
                           max_new_tokens=2))
    eng.run_until_done()
    progs = eng.executor.compiled_programs()
    assert progs["prefill"] <= 2, progs
    assert eng.metrics()["prefill_programs"] == progs["prefill"]
    assert eng.metrics()["prefill_batching"] == 1


# --------------------------------------------- batch-admit semantics

def test_batch_admit_shares_t_first(gdn_model):
    """Rows admitted by one batched dispatch are one device event: both
    requests sync through the SAME host read and stamp the SAME
    ``t_first`` (serial stamps would skew TTFT for all but the first
    row)."""
    cfg, params = gdn_model
    eng = DecodeEngine(cfg, params, max_slots=2, max_len=64,
                       decode_block=4, overlap=True, prefill_chunk=8)
    reqs = [Request(rid=i, prompt=np.arange(1, 18, dtype=np.int32),
                    max_new_tokens=4) for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    assert all(r.t_first is not None for r in reqs)
    assert reqs[0].t_first == reqs[1].t_first


def test_multirow_scatter_single_dispatch(gdn_model):
    """Every finished staging row enters its slot in ONE dispatch: two
    simultaneously-admitted requests cost one scatter (the per-prompt
    path pays one per request), and the prefill itself costs one scan +
    one admit dispatch regardless of row count."""
    cfg, params = gdn_model
    eng = DecodeEngine(cfg, params, max_slots=2, max_len=64,
                       decode_block=4, overlap=True, prefill_chunk=8)
    reqs = [Request(rid=i, prompt=np.arange(1, 18, dtype=np.int32),
                    max_new_tokens=6) for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    assert eng.scatter_dispatches == 1
    assert eng.stage_dispatches == 2        # one bscan + one badmit
    eng.run_until_done()
    assert all(r.done for r in reqs)

    per = DecodeEngine(cfg, params, max_slots=2, max_len=64,
                       decode_block=4, overlap=True, prefill_chunk=8,
                       prefill_batching=False)
    reqs2 = [Request(rid=i, prompt=np.arange(1, 18, dtype=np.int32),
                     max_new_tokens=6) for i in range(2)]
    for r in reqs2:
        per.submit(r)
    per.run_until_done()
    assert per.scatter_dispatches == 2
    assert per.stage_dispatches == 4
    assert [r.output for r in reqs2] == [r.output for r in reqs]


def test_fairness_long_prompt_drains_oldest_first(gdn_model):
    """Strict oldest-first packing: under saturation with a 1-chunk
    budget and short prompts arriving continuously behind it, a long
    staged prompt still drains one chunk every tick — its first token
    lands within (chunks + 1) saturated ticks and BEFORE any
    later-arriving short prompt's, so its dispatch count is bounded by
    its own chunk count."""
    cfg, params = gdn_model
    eng = DecodeEngine(cfg, params, max_slots=1, max_len=64,
                       decode_block=4, overlap=True, prefill_chunk=8,
                       staging_depth=2, prefill_budget=8)
    busy = Request(rid=99, prompt=np.arange(1, 9, dtype=np.int32),
                   max_new_tokens=50)
    eng.submit(busy)
    eng.step()                                  # slot busy, long budget
    long = Request(rid=0, prompt=np.arange(1, 58, dtype=np.int32),
                   max_new_tokens=4)            # 57 tokens = 7 chunks + 1
    eng.submit(long)
    shorts = []
    ticks = 0
    while long.t_first is None and ticks < 12:
        s = Request(rid=1 + ticks, prompt=np.arange(1, 7, dtype=np.int32),
                    max_new_tokens=2)
        eng.submit(s)                           # continuous arrivals
        shorts.append(s)
        eng.step()
        ticks += 1
    assert long.t_first is not None, "long prompt starved"
    assert ticks <= 9, f"long prompt took {ticks} saturated ticks"
    assert all(s.t_first is None for s in shorts), \
        "a younger short prompt was admitted before the older long one"
    eng.run_until_done(max_ticks=50_000)
    assert long.done and busy.done and all(s.done for s in shorts)


# --------------------------------------------------------------- gates

def test_capability_flag_gates_batching(gdn_model, monkeypatch):
    """A mixer kind without per-row (B,) valid_len support keeps the
    engine on per-prompt staging: silently on auto, with a loud warning
    when batching is forced — and it still serves."""
    from repro.models.mixers.gdn import GatedDeltaNet
    cfg, params = gdn_model
    monkeypatch.setattr(GatedDeltaNet, "supports_batched_ragged_prefill",
                        False)
    eng = DecodeEngine(cfg, params, max_slots=1, max_len=64,
                       decode_block=1, prefill_chunk=8)
    assert not eng.prefill_batching         # auto: silent fallback
    with pytest.warns(RuntimeWarning, match="prefill_batching disabled"):
        eng = DecodeEngine(cfg, params, max_slots=1, max_len=64,
                           decode_block=1, prefill_chunk=8,
                           prefill_batching=True)
    assert not eng.prefill_batching
    eng.submit(Request(rid=0, prompt=np.arange(1, 12, dtype=np.int32),
                       max_new_tokens=2))
    assert all(r.done for r in eng.run_until_done())


def test_moe_gate_disables_batching():
    """MoE expert-capacity dispatch couples rows within a batch (the
    cumsum queue), so batched prefill cannot be bitwise — the gate
    keeps MoE archs on per-prompt staging."""
    cfg = configs.get_arch("mixtral-8x7b").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(cfg, params, max_slots=1, max_len=64,
                       decode_block=1, prefill_chunk=8)
    assert not eng.prefill_batching
    with pytest.warns(RuntimeWarning, match="expert-capacity"):
        eng = DecodeEngine(cfg, params, max_slots=1, max_len=64,
                           decode_block=1, prefill_chunk=8,
                           prefill_batching=True)
    assert not eng.prefill_batching


def test_prefill_budget_validation(gdn_model):
    cfg, params = gdn_model
    with pytest.raises(ValueError, match="prefill_budget"):
        DecodeEngine(cfg, params, max_slots=1, max_len=32,
                     prefill_budget=0)


# ----------------------------------------- multi-device (subprocess, 8x)

SUBPROCESS_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np
    from repro import configs
    from repro.models import lm
    from repro.launch.mesh import make_mesh
    from repro.serving.engine import DecodeEngine, Request

    cfg = configs.get_arch("qwen3-next-gdn").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)

    def serve(mesh, batching, stochastic, depth=2):
        eng = DecodeEngine(cfg, params, max_slots=8, max_len=64,
                           decode_block=4, prefill_chunk=8, mesh=mesh,
                           staging_depth=depth, prefill_batching=batching)
        reqs = [Request(rid=i,
                        prompt=np.arange(1, 7 + 3 * i, dtype=np.int32),
                        max_new_tokens=4 + i,
                        temperature=0.8 if stochastic and i % 2 else 0.0,
                        top_k=10 if stochastic and i % 2 else 0)
                for i in range(6)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        assert all(r.done for r in reqs)
        return eng, [list(r.output) for r in reqs]

    # --- 1. data-sharded batched serving is bitwise: 8-device mesh,
    #        batched == per-prompt == 1-device baseline, greedy and
    #        stochastic, at a dividing (8) and a non-dividing (2,
    #        row-replicated) staging depth
    mesh1 = make_mesh((1, 1), ("data", "model"),
                      devices=jax.devices()[:1])
    mesh8 = make_mesh((8, 1), ("data", "model"))
    for stochastic in (False, True):
        _, base = serve(mesh1, None, stochastic)
        _, per8 = serve(mesh8, False, stochastic)
        assert per8 == base
        for depth in (2, 8):
            eng8, bat8 = serve(mesh8, None, stochastic, depth=depth)
            assert eng8.prefill_batching
            assert bat8 == base, (
                f"batched slot-axis DP must be bitwise "
                f"(stochastic={stochastic}, depth={depth})")

    # --- 2. batched staging rows shard on "data" when the depth
    #        divides, and never land a DP axis on a state dim otherwise
    def ax(e):
        return () if e is None else (e if isinstance(e, tuple) else (e,))
    eng8, _ = serve(mesh8, None, False, depth=8)
    flat, _ = jax.tree_util.tree_flatten_with_path(eng8.executor.bstaging)
    from repro.parallel import sharding as rules
    spec_of = {rules.path_str(p): l.sharding.spec for p, l in flat}
    s_specs = [s for p, s in spec_of.items() if p.endswith("/S")]
    assert s_specs and all(ax(s[1]) == ("data",) for s in s_specs), s_specs
    eng2, _ = serve(mesh8, None, False, depth=2)
    flat2, _ = jax.tree_util.tree_flatten_with_path(eng2.executor.bstaging)
    assert all(not any("data" in ax(e) for e in l.sharding.spec)
               for _, l in flat2)

    # --- 3. head-sharded (4, 2): batched serving completes (model-axis
    #        psum ordering, checked at completion like any TP stack)
    mesh42 = make_mesh((4, 2), ("data", "model"))
    eng42, out42 = serve(mesh42, None, False, depth=4)
    assert eng42.prefill_batching
    assert all(len(o) == 4 + i for i, o in enumerate(out42))

    print("SUBPROCESS_BATCHED_OK")
""")


def test_sharded_batched_serving_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SUBPROCESS_TEST],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=1800)
    assert "SUBPROCESS_BATCHED_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]
