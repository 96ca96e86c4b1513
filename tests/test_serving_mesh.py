"""Mesh-sharded serving: slot-axis DP + head-sharded state, router, ring.

Three layers of coverage:

  * **in-process** (single device): topology parsing/padding, up-front
    mesh-shape validation, the staging-buffer ring (depth knob, parity,
    multiple outstanding ahead-of-slot prefills), and the router
    (placement policies, rebalance, drain, metrics aggregation).
  * **subprocess** (8 virtual CPU devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the
    ``test_parallel.py`` idiom): bitwise token-stream parity between a
    1-device mesh and an 8-device data-sharded mesh for greedy *and*
    stochastic sampling; numeric parity (float-reduction tolerance) plus
    end-to-end completion for the head-sharded (4, 2) mesh; and buffer
    sharding placement assertions (slot axis on "data", state heads /
    KV context on "model").

The data axis moves *placement* only — per-slot arithmetic is unchanged,
so streams are bitwise identical.  The model axis splits head/context
reductions (psum partial ordering), so it is checked at float tolerance,
like any tensor-parallel serving stack.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServingTopology
from repro.launch import mesh as mesh_mod
from repro.models import lm
from repro.serving.engine import DecodeEngine, Request, Router


@pytest.fixture(scope="module")
def gdn_model():
    cfg = configs.get_arch("qwen3-next-gdn").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(n, stochastic=False):
    return [Request(rid=i, prompt=np.arange(1, 7 + 3 * i, dtype=np.int32),
                    max_new_tokens=4 + i,
                    temperature=0.8 if stochastic and i % 2 else 0.0,
                    top_k=10 if stochastic and i % 2 else 0)
            for i in range(n)]


# ------------------------------------------------------------- topology

def test_topology_parse_and_pad():
    t = ServingTopology.parse("4,2")
    assert t.shape == (4, 2) and t.axes == ("data", "model")
    assert t.devices == 8
    t = ServingTopology.parse("data=2,model=3", staging_depth=3)
    assert (t.data, t.model, t.staging_depth) == (2, 3, 3)
    assert ServingTopology(data=4).pad_slots(5) == 8
    assert ServingTopology(data=4).pad_slots(8) == 8
    assert ServingTopology().pad_slots(3) == 3
    for bad in ("4", "4,2,1", "data=4,oops=2", "0,2", "a,b"):
        with pytest.raises(ValueError):
            ServingTopology.parse(bad)


def test_validate_mesh_shape_up_front():
    """A bad topology must fail with an actionable one-liner before any
    jit sees the mesh (it used to surface deep inside partitioning)."""
    assert mesh_mod.validate_mesh_shape((1, 1), ("data", "model")) == (1, 1)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        mesh_mod.validate_mesh_shape((4, 2), ("data", "model"),
                                     device_count=1)
    with pytest.raises(ValueError, match="positive int"):
        mesh_mod.validate_mesh_shape((0, 2), ("data", "model"))
    with pytest.raises(ValueError, match="axes"):
        mesh_mod.validate_mesh_shape((2, 2, 2), ("data", "model"))
    with pytest.raises(ValueError, match="duplicate"):
        mesh_mod.validate_mesh_shape((2, 2), ("data", "data"),
                                     device_count=4)
    if jax.device_count() < 4:              # single-device test process
        with pytest.raises(ValueError, match="needs 4 devices"):
            mesh_mod.make_serving_mesh(2, 2)


# --------------------------------------------------------- staging ring

def _serve(cfg, params, *, staging_depth, overlap=True, stochastic=False,
           n=6, slots=2):
    eng = DecodeEngine(cfg, params, max_slots=slots, max_len=64,
                       decode_block=4, overlap=overlap, prefill_chunk=8,
                       staging_depth=staging_depth)
    reqs = _reqs(n, stochastic)
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return eng, [list(r.output) for r in reqs]


def test_staging_ring_parity(gdn_model):
    """Ring depth moves *when* prefills run, never what is computed:
    token streams are bitwise identical across depths (and vs the
    serialized baseline)."""
    cfg, params = gdn_model
    _, base = _serve(cfg, params, staging_depth=1, overlap=False)
    for depth in (1, 2, 3):
        _, out = _serve(cfg, params, staging_depth=depth)
        assert out == base, f"depth={depth} diverged"
    _, st = _serve(cfg, params, staging_depth=2, stochastic=True)
    _, st1 = _serve(cfg, params, staging_depth=1, stochastic=True)
    assert st == st1


def test_staging_ring_multiple_outstanding(gdn_model):
    """Under saturation a depth-2 ring keeps two ahead-of-slot prefills
    in flight (the single-buffer executor could only hold one)."""
    cfg, params = gdn_model
    eng = DecodeEngine(cfg, params, max_slots=1, max_len=64,
                       decode_block=4, overlap=True, prefill_chunk=8,
                       staging_depth=2)
    eng.submit(Request(rid=9, prompt=np.arange(1, 18, dtype=np.int32),
                       max_new_tokens=40))
    eng.step()                                  # slot occupied, decoding
    for i in range(3):
        eng.submit(Request(rid=i, prompt=np.arange(1, 18, dtype=np.int32),
                           max_new_tokens=4))
    eng.step()
    # both ring buffers staging (third request still queued), slot busy
    assert len(eng._stagings) == 2
    assert len(eng.queue) == 1
    eng.step()                                  # 17-token plans complete:
    # both staged requests have their first token before any slot frees
    first_two = [r for r in eng._all if r.rid in (0, 1)]
    assert all(len(r.output) == 1 for r in first_two)
    assert not any(r.done for r in eng._all if r.rid == 9)
    eng.run_until_done()
    assert all(r.done for r in eng._all)


def test_staging_depth_validation(gdn_model):
    cfg, params = gdn_model
    with pytest.raises(ValueError, match="staging_depth"):
        DecodeEngine(cfg, params, max_slots=1, max_len=32, staging_depth=0)


def test_metrics_report_topology(gdn_model):
    cfg, params = gdn_model
    eng = DecodeEngine(cfg, params, max_slots=2, max_len=32,
                       staging_depth=3)
    m = eng.metrics()
    assert m["staging_depth"] == 3
    assert m["mesh_data"] == 1 and m["mesh_model"] == 1


# --------------------------------------------------------------- router

def _mini_engine(cfg, params, slots=2):
    return DecodeEngine(cfg, params, max_slots=slots, max_len=64,
                        decode_block=2, prefill_chunk=8)


def test_router_round_robin_placement(gdn_model):
    cfg, params = gdn_model
    r = Router([_mini_engine(cfg, params) for _ in range(3)],
               policy="round_robin")
    idxs = [r.submit(q) for q in _reqs(6)]
    assert idxs == [0, 1, 2, 0, 1, 2]
    assert r.placed == [2, 2, 2]


def test_router_least_loaded_placement(gdn_model):
    cfg, params = gdn_model
    engs = [_mini_engine(cfg, params) for _ in range(2)]
    r = Router(engs)                      # least_loaded is the default
    # preload engine 0 with two requests -> next three go 1, 1, 0
    r.engines[0].submit(Request(rid=90, prompt=np.arange(1, 9,
                                                         dtype=np.int32)))
    r.engines[0].submit(Request(rid=91, prompt=np.arange(1, 9,
                                                         dtype=np.int32)))
    idxs = [r.submit(q) for q in _reqs(3)]
    assert idxs == [1, 1, 0]


def test_router_rebalance_on_shard_full(gdn_model):
    """Queued requests migrate from a shard-full engine to an idle one;
    t_submit survives the move so TTFT measures the client's wait."""
    cfg, params = gdn_model
    engs = [_mini_engine(cfg, params, slots=1) for _ in range(2)]
    r = Router(engs, policy="round_robin")
    # jam engine 0: one active (via step) + two queued behind it
    busy = Request(rid=50, prompt=np.arange(1, 9, dtype=np.int32),
                   max_new_tokens=30)
    engs[0].submit(busy)
    engs[0].step()
    q1 = Request(rid=51, prompt=np.arange(1, 9, dtype=np.int32),
                 max_new_tokens=4)
    q2 = Request(rid=52, prompt=np.arange(1, 9, dtype=np.int32),
                 max_new_tokens=4)
    engs[0].submit(q1)
    engs[0].submit(q2)
    t_orig = q2.t_submit
    moved = r.rebalance()
    assert moved >= 1
    assert r.migrated == moved
    # tail request moved to the idle engine, head kept FIFO position
    assert q2 in engs[1].queue or q2 in engs[1]._all
    assert q2.t_submit == t_orig
    assert engs[0].queue and engs[0].queue[0] is q1
    done = r.run_until_done()
    assert {q.rid for q in done} == {50, 51, 52}


def test_router_drain(gdn_model):
    cfg, params = gdn_model
    engs = [_mini_engine(cfg, params) for _ in range(2)]
    r = Router(engs, policy="round_robin")
    for q in _reqs(4):
        r.submit(q)                 # 2 queued on each engine
    moved = r.drain(0)
    assert moved == 2
    assert not engs[0].queue
    assert len(engs[1].queue) == 4
    # new submissions skip the draining engine
    extra = Request(rid=99, prompt=np.arange(1, 9, dtype=np.int32),
                    max_new_tokens=2)
    assert r.submit(extra) == 1
    r.undrain(0)
    with pytest.raises(RuntimeError, match="draining"):
        rr = Router([_mini_engine(cfg, params)])
        rr.drain(0)


def test_router_metrics_aggregate(gdn_model):
    cfg, params = gdn_model
    engs = [_mini_engine(cfg, params) for _ in range(2)]
    r = Router(engs, policy="round_robin")
    reqs = _reqs(4)
    for q in reqs:
        r.submit(q)
    done = r.run_until_done()
    assert len(done) == 4 and all(q.done for q in reqs)
    m = r.metrics()
    per = m["per_engine"]
    assert m["engines"] == 2 and len(per) == 2
    assert m["requests"] == per[0]["requests"] + per[1]["requests"] == 4
    assert m["tokens"] == sum(p["tokens"] for p in per)
    assert m["ticks"] == sum(p["ticks"] for p in per)
    assert m["decoded_tokens"] == sum(p["decoded_tokens"] for p in per)
    assert m["placed"] == [2, 2]
    assert m["mean_ttft_s"] > 0.0
    # single-engine router == the engine itself (same streams)
    single = _mini_engine(cfg, params)
    rs = Router([single])
    reqs2 = _reqs(4)
    for q in reqs2:
        rs.submit(q)
    rs.run_until_done()
    by_rid = {q.rid: q.output for q in reqs}
    assert all(by_rid[q.rid] == q.output for q in reqs2)


def test_router_migration_rejection_keeps_request(gdn_model):
    """A heterogeneous taker (smaller max_len) rejecting a migrated
    request must not drop it: it goes back on the donor's queue."""
    cfg, params = gdn_model
    donor = _mini_engine(cfg, params, slots=1)
    small = DecodeEngine(cfg, params, max_slots=2, max_len=8,
                         decode_block=2, prefill_chunk=8)
    r = Router([donor, small], policy="round_robin")
    busy = Request(rid=1, prompt=np.arange(1, 9, dtype=np.int32),
                   max_new_tokens=20)
    donor.submit(busy)
    donor.step()                            # slot busy
    long = Request(rid=2, prompt=np.arange(1, 15, dtype=np.int32),
                   max_new_tokens=2)        # 14 tokens > small's max_len
    donor.submit(long)
    with pytest.warns(RuntimeWarning, match="rejected migrated"):
        moved = r.rebalance()
    assert moved == 0
    assert long in donor.queue and long in donor._all
    done = r.run_until_done()
    assert {q.rid for q in done} == {1, 2}


def test_withdraw_keeps_metrics_watermark(gdn_model):
    """Withdrawing a pre-reset request must not shift post-reset requests
    out of the metrics window."""
    cfg, params = gdn_model
    eng = _mini_engine(cfg, params)
    a = Request(rid=1, prompt=np.arange(1, 9, dtype=np.int32),
                max_new_tokens=2)
    eng.submit(a)
    eng.reset_metrics()                     # watermark past the queued a
    b = Request(rid=2, prompt=np.arange(1, 9, dtype=np.int32),
                max_new_tokens=2)
    eng.submit(b)
    assert eng.withdraw(oldest=True) is a   # a leaves; watermark follows
    eng.run_until_done()
    m = eng.metrics()
    assert m["requests"] == 1 and b.done


def test_router_validation(gdn_model):
    cfg, params = gdn_model
    with pytest.raises(ValueError, match="at least one"):
        Router([])
    with pytest.raises(ValueError, match="policy"):
        Router([_mini_engine(cfg, params)], policy="random")


# ----------------------------------------- multi-device (subprocess, 8x)

SUBPROCESS_TEST = textwrap.dedent("""
    import os, warnings
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.models import lm
    from repro.parallel import sharding as rules
    from repro.launch.mesh import make_mesh
    from repro.serving.engine import DecodeEngine, Request

    cfg = configs.get_arch("qwen3-next-gdn").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)

    def serve(mesh, stochastic, slots=8):
        eng = DecodeEngine(cfg, params, max_slots=slots, max_len=64,
                           decode_block=4, prefill_chunk=8, mesh=mesh)
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

    # --- 1. bitwise parity: 1-device mesh == 8-device data-sharded mesh
    mesh1 = make_mesh((1, 1), ("data", "model"),
                      devices=jax.devices()[:1])
    mesh8 = make_mesh((8, 1), ("data", "model"))
    for stochastic in (False, True):
        _, base = serve(mesh1, stochastic)
        eng8, out8 = serve(mesh8, stochastic)
        assert out8 == base, (
            f"slot-axis DP must be bitwise (stochastic={stochastic}):"
            f" {out8} vs {base}")

    # --- 2. buffer placement: slot axis on data, state heads / KV
    #        context on model
    mesh42 = make_mesh((4, 2), ("data", "model"))
    eng42, out42 = serve(mesh42, False)

    def ax(entry):          # normalize a PartitionSpec entry to a tuple
        if entry is None:
            return ()
        return entry if isinstance(entry, tuple) else (entry,)

    flat, _ = jax.tree_util.tree_flatten_with_path(eng42.executor.caches)
    spec_of = {rules.path_str(p): l.sharding.spec for p, l in flat}
    s_specs = [s for p, s in spec_of.items() if p.endswith("/S")]
    kv_specs = [s for p, s in spec_of.items()
                if p.endswith("/k") or p.endswith("/v")]
    assert s_specs and all(ax(s[1]) == ("data",) and ax(s[2]) == ("model",)
                           for s in s_specs), s_specs
    assert kv_specs and all(ax(s[1]) == ("data",) and ax(s[3]) == ("model",)
                            for s in kv_specs), kv_specs
    assert ax(eng42.executor.tokens.sharding.spec[0]) == ("data",)
    assert ax(eng42.executor.sampler["key"].sharding.spec[0]) == ("data",)
    # staging ring: replicated on the slot axis, same model placement
    st_flat, _ = jax.tree_util.tree_flatten_with_path(
        eng42.executor.staging[0])
    st_specs = [l.sharding.spec for _, l in st_flat]
    assert all(len(s) < 2 or ax(s[1]) == () for s in st_specs)
    assert any(any(ax(e) == ("model",) for e in s) for s in st_specs)
    assert eng42.metrics()["mesh_data"] == 4
    assert eng42.metrics()["mesh_model"] == 2

    # --- 3. head-sharded numerics: same math to float-reduction order
    #        (psum partials), like any TP stack — checked at tolerance
    S = 8
    caches = lm.init_caches(cfg, S, 64)
    tok = jnp.arange(1, S + 1, dtype=jnp.int32)
    logits_ref, _ = jax.jit(
        lambda p, t, c: lm.decode_step(p, cfg, t, c))(params, tok, caches)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), caches)
    cache_sh = rules.make_shardings(
        mesh42, rules.cache_specs(cfg, mesh42, shapes, S))
    p_sh = rules.make_shardings(
        mesh42, rules.params_specs(cfg, params, False, mesh42))
    tok_sh = NamedSharding(mesh42, P("data"))
    logits_s, _ = jax.jit(
        lambda p, t, c: lm.decode_step(p, cfg, t, c),
        in_shardings=(p_sh, tok_sh, cache_sh))(
            jax.device_put(params, p_sh), jax.device_put(tok, tok_sh),
            jax.device_put(caches, cache_sh))
    np.testing.assert_allclose(np.asarray(logits_ref),
                               np.asarray(logits_s), rtol=2e-4, atol=2e-4)

    # --- 4. non-dividing slot count: loud warning, still completes (the
    #        dropped data annotation may be re-placed on a state dim by
    #        fit_spec, so bitwise parity is only promised for padded
    #        counts — ServingTopology.pad_slots)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _, out_odd = serve(mesh8, False, slots=6)
    assert any("pad_slots" in str(x.message) for x in w)
    assert all(len(o) == 4 + i for i, o in enumerate(out_odd))

    print("SUBPROCESS_MESH_OK")
""")


def test_sharded_serving_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SUBPROCESS_TEST],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=1800)
    assert "SUBPROCESS_MESH_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]


# ------------------------------------ state paging under a mesh (subproc)

SUBPROCESS_PAGING_TEST = textwrap.dedent("""
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

    def reqs():
        # rid 0 — the paused one — samples stochastically: the swapped
        # image must round-trip the PRNG key mid-stream
        return [Request(rid=i,
                        prompt=np.arange(1, 7 + 3 * i, dtype=np.int32),
                        max_new_tokens=6 + i,
                        temperature=0.8 if i % 2 == 0 else 0.0,
                        top_k=10 if i % 2 == 0 else 0,
                        top_p=0.9 if i % 2 == 0 else 1.0)
                for i in range(6)]

    def serve(mesh, paged):
        eng = DecodeEngine(cfg, params, max_slots=4, max_len=64,
                           decode_block=4, prefill_chunk=8, mesh=mesh)
        rr = reqs()
        for q in rr:
            eng.submit(q)
        if paged:
            for _ in range(50):
                eng.step()
                if rr[0].state == "active" and len(rr[0].output) >= 2:
                    break
            assert rr[0].state == "active", rr[0].state
            eng.pause(0)
            sw = eng.swapped[0].state
            # gathered under a mesh, the host image is plain replicated
            # numpy — topology-free, restorable on any same-cfg engine
            assert all(isinstance(x, np.ndarray)
                       for x in jax.tree.leaves(sw.caches))
            eng.step()
            eng.resume(0)
        eng.run_until_done()
        assert all(q.done for q in rr)
        return eng, [list(q.output) for q in rr]

    mesh1 = make_mesh((1, 1), ("data", "model"),
                      devices=jax.devices()[:1])
    mesh4 = make_mesh((4, 1), ("data", "model"),
                      devices=jax.devices()[:4])

    # --- 1. bitwise parity: pause/resume on a 1-device mesh AND a
    #        4-device data-sharded mesh both reproduce the uninterrupted
    #        1-device streams exactly
    _, base = serve(mesh1, False)
    for mesh in (mesh1, mesh4):
        _, out = serve(mesh, True)
        assert out == base, (out, base)

    # --- 2. placement restored leaf-by-leaf: after a swap-out/swap-in
    #        round trip every slot buffer carries the same NamedSharding
    #        spec as an engine that never swapped
    eng_ref, _ = serve(mesh4, False)
    eng_sw, _ = serve(mesh4, True)
    ref = [l.sharding.spec
           for l in jax.tree.leaves(eng_ref.executor.caches)]
    got = [l.sharding.spec
           for l in jax.tree.leaves(eng_sw.executor.caches)]
    assert got == ref, list(zip(got, ref))[:4]
    assert (eng_sw.executor.tokens.sharding.spec
            == eng_ref.executor.tokens.sharding.spec)
    for k in eng_sw.executor.sampler:
        assert (eng_sw.executor.sampler[k].sharding.spec
                == eng_ref.executor.sampler[k].sharding.spec), k
    m = eng_sw.metrics()
    assert m["swap_outs"] >= 1 and m["swap_ins"] >= 1
    assert m["swap_bytes"] >= 2 * eng_sw.executor.swap_bytes_per_slot
    print("SUBPROCESS_PAGING_OK")
""")


def test_sharded_swap_subprocess():
    """Swap/resume on a data-sharded mesh: bitwise parity with the
    1-device run, and sharding placement restored leaf-by-leaf."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SUBPROCESS_PAGING_TEST],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=1800)
    assert "SUBPROCESS_PAGING_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]


# ------------------------------- async state paging under a mesh (subproc)

SUBPROCESS_ASYNC_PAGING_TEST = textwrap.dedent("""
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

    def reqs():
        # rid 0 — the paused one — samples stochastically: the swapped
        # image must round-trip the PRNG key mid-stream
        return [Request(rid=i,
                        prompt=np.arange(1, 7 + 3 * i, dtype=np.int32),
                        max_new_tokens=6 + i,
                        temperature=0.8 if i % 2 == 0 else 0.0,
                        top_k=10 if i % 2 == 0 else 0,
                        top_p=0.9 if i % 2 == 0 else 1.0)
                for i in range(6)]

    def serve(mesh, paged, async_paging=False):
        eng = DecodeEngine(cfg, params, max_slots=4, max_len=64,
                           decode_block=4, prefill_chunk=8, mesh=mesh,
                           async_paging=async_paging)
        rr = reqs()
        for q in rr:
            eng.submit(q)
        if paged:
            for _ in range(50):
                eng.step()
                if rr[0].state == "active" and len(rr[0].output) >= 2:
                    break
            assert rr[0].state == "active", rr[0].state
            eng.pause(0)
            if async_paging:
                # slot freed at dispatch; the D2H drain is in flight
                assert eng.swapped[0].pending is not None
            eng.step()
            eng.resume(0)
        eng.run_until_done()
        assert all(q.done for q in rr)
        return eng, [list(q.output) for q in rr]

    mesh1 = make_mesh((1, 1), ("data", "model"),
                      devices=jax.devices()[:1])
    mesh4 = make_mesh((4, 1), ("data", "model"),
                      devices=jax.devices()[:4])

    # --- 1. bitwise parity: ASYNC pause/resume on a 1-device mesh and a
    #        4-device data-sharded mesh both reproduce the synchronous
    #        1-device paged run — which itself reproduces the
    #        uninterrupted base streams exactly
    _, base = serve(mesh1, False)
    _, sync1 = serve(mesh1, True, async_paging=False)
    assert sync1 == base, (sync1, base)
    for mesh in (mesh1, mesh4):
        eng, out = serve(mesh, True, async_paging=True)
        assert out == base, (out, base)
        m = eng.metrics()
        assert m["async_paging"] == 1 and m["swap_outs"] >= 1

    # --- 2. a prestaged (prefetched) restore image carries the
    #        canonical staging placements leaf-by-leaf — the
    #        grant-boundary scatter must consume it with zero relayout
    eng = DecodeEngine(cfg, params, max_slots=4, max_len=64,
                       decode_block=4, prefill_chunk=8, mesh=mesh4,
                       async_paging=True)
    rr = reqs()
    for q in rr:
        eng.submit(q)
    for _ in range(50):
        eng.step()
        if rr[0].state == "active" and len(rr[0].output) >= 2:
            break
    assert rr[0].state == "active", rr[0].state
    eng.pause(0)
    eng.flush_swaps()            # harvest the drain so prestage can run
    eng.resume(0)
    eng._prefetch_resume()       # slot is free -> grant is predictable
    rec = eng.swapped[0]
    assert rec.prefetch is not None, "prefetch did not stage"
    st, row, tok = rec.prefetch
    x = eng.executor
    got = [l.sharding for l in jax.tree.leaves(st)]
    want = jax.tree.leaves(x._sh_staging)
    assert len(got) == len(want) and got == want, \
        list(zip(got, want))[:4]
    row_got = [l.sharding for l in jax.tree.leaves(row)]
    row_want = jax.tree.leaves(x._sh_row)
    assert row_got == row_want, list(zip(row_got, row_want))[:4]
    assert tok.sharding == x._sh_rep, tok.sharding
    assert eng.metrics()["swap_prefetches"] >= 1
    eng.run_until_done()
    assert [list(q.output) for q in rr] == base
    assert eng.metrics()["swap_prefetch_hits"] >= 1
    print("SUBPROCESS_ASYNC_PAGING_OK")
""")


def test_sharded_async_swap_subprocess():
    """Async pause/resume on a data-sharded mesh: streams bitwise equal
    to the 1-device synchronous paged run, and a prefetched restore
    image's leaf shardings match the executor's canonical staging /
    sampler-row / replicated placements."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c",
                        SUBPROCESS_ASYNC_PAGING_TEST],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=1800)
    assert "SUBPROCESS_ASYNC_PAGING_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]


# ------------------------------ speculative decode under a mesh (subproc)

SUBPROCESS_SPEC_TEST = textwrap.dedent("""
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

    def serve(mesh, speculative, stochastic):
        eng = DecodeEngine(cfg, params, max_slots=4, max_len=64,
                           decode_block=4, prefill_chunk=8, mesh=mesh,
                           speculative=speculative, k_draft=4)
        rr = [Request(rid=i,
                      prompt=np.arange(1, 7 + 3 * i, dtype=np.int32),
                      max_new_tokens=6 + i,
                      temperature=0.8 if stochastic and i % 2 == 0 else 0.0,
                      top_k=10 if stochastic and i % 2 == 0 else 0,
                      top_p=0.9 if stochastic and i % 2 == 0 else 1.0)
              for i in range(6)]
        for q in rr:
            eng.submit(q)
        eng.run_until_done()
        assert all(q.done for q in rr)
        return eng, [list(q.output) for q in rr]

    mesh1 = make_mesh((1, 1), ("data", "model"),
                      devices=jax.devices()[:1])
    mesh4 = make_mesh((4, 1), ("data", "model"),
                      devices=jax.devices()[:4])

    # --- 1. bitwise parity: data-sharded speculative streams == the
    #        1-device non-speculative streams, greedy AND stochastic
    for stochastic in (False, True):
        _, base = serve(mesh1, False, stochastic)
        for mesh in (mesh1, mesh4):
            eng, out = serve(mesh, True, stochastic)
            assert out == base, (
                f"speculative mesh decode must be bitwise "
                f"(stochastic={stochastic}): {out} vs {base}")
            assert eng.metrics()["acceptance_rate"] > 0.5

    # --- 2. checkpoint/draft buffers carry the same placements as the
    #        slot caches (checkpoint_specs == cache_specs rules), so the
    #        commit/rollback select and the cache<->ckpt ping-pong stay
    #        communication-free
    eng, _ = serve(mesh4, True, False)
    x = eng.executor
    cache_specs = [l.sharding.spec for l in jax.tree.leaves(x.caches)]
    ckpt_specs = [l.sharding.spec for l in jax.tree.leaves(x.ckpt)]
    assert ckpt_specs == cache_specs, list(zip(ckpt_specs,
                                               cache_specs))[:4]
    d_specs = [l.sharding.spec for l in jax.tree.leaves(x.dcaches)]
    dk_specs = [l.sharding.spec for l in jax.tree.leaves(x.dckpt)]
    assert dk_specs == d_specs
    slot_ax = [s[1] for s in d_specs if len(s) > 1]
    assert slot_ax and all(a in ("data", ("data",)) for a in slot_ax), \\
        slot_ax
    print("SUBPROCESS_SPEC_OK")
""")


def test_sharded_spec_decode_subprocess():
    """Speculative decode on a data-sharded mesh: streams bitwise equal
    to the 1-device non-speculative run, and the rollback checkpoint /
    draft buffers share the slot caches' placements."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SUBPROCESS_SPEC_TEST],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=1800)
    assert "SUBPROCESS_SPEC_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]
