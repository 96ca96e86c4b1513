#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU, at the published width of
qwen3-next-gdn (48 layers, d_model 2048, bf16 weights from a seed).

    python3 chip_smoke.py             # one chip: phases (a)-(d)
    python3 chip_smoke.py --chips 4   # four chips: the mesh-sharded engine

One chip, one process:

  (a) device: the default backend must be a TPU (anything else exits
      non-zero and prints no result);
  (b) kernels compiled on the chip: ``gdn_decode``, ``gdn_prefill`` (with
      a ragged ``valid_len``) and ``attn_decode`` at qwen3-next-gdn widths,
      and the ``delta_rule=False`` decode/prefill at mamba2-1.3b widths,
      each against ``kernels/ref.py`` in float32;
  (c) serving, XLA path: the engine ``launch/serve.py`` builds, 4 slots,
      6 requests of 16 new tokens, decode_block 4;
  (d) serving, kernel path (``use_pallas_serving=True``): one ``lm.prefill``
      + ``lm.decode_step`` against the XLA path on the same weights, then
      the same engine run.

``--chips 4`` runs only the mesh-sharded engine (slot axis on ``data``,
state heads and KV on ``model``) on meshes data=4 and data=2,model=2, each
against a one-chip engine on device 0 in the same process.

Any failure raises.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-next-gdn"
SEED = 0
# normalized max error, max|x - ref| / max|ref|
KERNEL_TOL = 1e-2        # Pallas kernel vs float32 ref.py (HIGHEST precision)
# A bf16 path under test (the kernel path, a mesh engine) may be at most
# PATH_RATIO times as far from the float32 reference model (same weights,
# float32 activations, HIGHEST matmul precision) as the bf16 one-chip XLA
# path is, or KERNEL_TOL, whichever is larger: two bf16 paths through 48
# random layers differ by a few percent of the logits' range, so a fixed
# bound between them says less than their distance from float32.
PATH_RATIO = 2.0


def log(msg: str):
    print(msg, flush=True)


def nerr(x, ref) -> float:
    import numpy as np
    x, ref = np.asarray(x, np.float32), np.asarray(ref, np.float32)
    assert np.all(np.isfinite(x)), "non-finite output"
    return float(np.max(np.abs(x - ref)) / max(np.max(np.abs(ref)), 1e-30))


def check(name: str, err: float, tol: float):
    log(f"  {name}: normalized max error {err:.3e} (tolerance {tol:g})")
    assert err <= tol, f"{name}: error {err} exceeds {tol}"


def phase_device(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"(a) device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (default backend is "
                         f"{d.platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but only "
                         f"{len(devs)} TPU device(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _lowers_to_kernel(fn, *args) -> bool:
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def phase_kernels():
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    assert not ops.interpret_mode(), "kernels would run in interpret mode"
    log("(b) kernels, compiled (interpret=False)")
    ks = iter(jax.random.split(jax.random.PRNGKey(SEED), 64))
    bf16, f32 = jnp.bfloat16, jnp.float32

    def normal(shape, dtype=f32, scale=1.0):
        return (jax.random.normal(next(ks), shape, f32) * scale).astype(dtype)

    def unit(shape):                     # L2-normalized rows, as the model
        x = normal(shape)
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).astype(bf16)

    def gates(shape):
        return (jnp.exp(-jax.nn.softplus(normal(shape))),
                jax.nn.sigmoid(normal(shape)))

    hp = jax.default_matmul_precision("highest")

    # --- decode: qwen3-next-gdn (GVA 16:32, d 128) and mamba2 (1:64, 128x64)
    B = 4
    for name, Hk, Hv, dk, dv, delta in (
            ("gdn_decode qwen3-next-gdn", 16, 32, 128, 128, True),
            ("gdn_decode mamba2-1.3b delta_rule=False", 1, 64, 128, 64,
             False)):
        q, k = unit((B, Hk, dk)), unit((B, Hk, dk))
        v = normal((B, Hv, dv), bf16)
        S = normal((B, Hv, dk, dv), scale=0.1)
        g, beta = gates((B, Hv))
        if not delta:
            beta = jnp.ones_like(beta)
        fn = lambda *a, delta=delta: ops.gdn_decode(*a, delta_rule=delta)
        assert _lowers_to_kernel(fn, q, k, v, S, g, beta)
        t0 = time.perf_counter()
        o, S_new = jax.block_until_ready(jax.jit(fn)(q, k, v, S, g, beta))
        dt = time.perf_counter() - t0
        with hp:
            o_ref, S_ref = ref.gdn_decode_ref(q, k, v, S, g, beta,
                                              delta_rule=delta)
        log(f"  {name}: B={B} Hk={Hk} Hv={Hv} d_k={dk} d_v={dv}, first "
            f"call incl. compile {dt:.2f}s")
        check(f"{name} o", nerr(o, o_ref), KERNEL_TOL)
        check(f"{name} S", nerr(S_new, S_ref), KERNEL_TOL)

    # --- prefill: two sequences, the second ragged (valid_len 77 of 128)
    B, T, chunk = 2, 128, 64
    vl = jnp.array([T, 77], jnp.int32)
    for name, Hk, Hv, dk, dv, delta in (
            ("gdn_prefill qwen3-next-gdn", 16, 32, 128, 128, True),
            ("gdn_prefill mamba2-1.3b delta_rule=False", 1, 64, 128, 64,
             False)):
        q, k = unit((B, T, Hk, dk)), unit((B, T, Hk, dk))
        v = normal((B, T, Hv, dv), bf16)
        lg = -jax.nn.softplus(normal((B, T, Hv)))
        beta = (jax.nn.sigmoid(normal((B, T, Hv))) if delta
                else jnp.ones((B, T, Hv), f32))
        S0 = normal((B, Hv, dk, dv), scale=0.1)
        fn = lambda *a, delta=delta: ops.gdn_prefill(
            *a[:6], chunk=chunk, delta_rule=delta, valid_len=a[6])
        args = (q, k, v, lg, beta, S0, vl)
        assert _lowers_to_kernel(fn, *args)
        t0 = time.perf_counter()
        O, S = jax.block_until_ready(jax.jit(fn)(*args))
        dt = time.perf_counter() - t0
        log(f"  {name}: B={B} T={T} chunk={chunk} valid_len={vl.tolist()}"
            f", first call incl. compile {dt:.2f}s")
        for b in range(B):               # ref layout: (Hv, n, d) per row
            n = int(vl[b])
            heads = lambda x: x[b, :n].transpose(1, 0, 2)
            with hp:
                O_ref, S_ref = ref.gdn_prefill_ref(
                    jnp.repeat(heads(q), Hv // Hk, 0),
                    jnp.repeat(heads(k), Hv // Hk, 0), heads(v),
                    lg[b, :n].T, beta[b, :n].T, S0[b], delta_rule=delta)
            check(f"{name} row {b} O[:{n}]",
                  nerr(O[b, :n].transpose(1, 0, 2), O_ref), KERNEL_TOL)
            check(f"{name} row {b} S", nerr(S[b], S_ref), KERNEL_TOL)

    # --- attention decode: qwen3-next-gdn attention widths, ragged lengths
    B, Hq, Hkv, d, Tc = 4, 16, 2, 128, 1024
    q = normal((B, Hq, d), bf16)
    kc, vc = normal((B, Hkv, Tc, d), bf16), normal((B, Hkv, Tc, d), bf16)
    length = jnp.array([1024, 700, 257, 1], jnp.int32)
    fn = lambda *a: ops.attn_decode(*a, block_t=256)
    assert _lowers_to_kernel(fn, q, kc, vc, length)
    t0 = time.perf_counter()
    o = jax.block_until_ready(jax.jit(fn)(q, kc, vc, length))
    dt = time.perf_counter() - t0
    with hp:
        o_ref = ref.attn_decode_ref(q.astype(f32), kc, vc, length)
    log(f"  attn_decode: B={B} Hq={Hq} Hkv={Hkv} d={d} T={Tc} "
        f"length={length.tolist()}, first call incl. compile {dt:.2f}s")
    check("attn_decode o", nerr(o, o_ref), KERNEL_TOL)


def _serve_argv(**over):
    argv = {"--arch": ARCH, "--slots": 4, "--requests": 6, "--max-new": 16,
            "--decode-block": 4, "--max-len": 128, "--seed": SEED}
    argv.update(over)
    return ["--full"] + [str(x) for kv in argv.items() for x in kv]


def _serve(args, cfg=None, params=None):
    """Build engines exactly as launch/serve.py does, serve, check."""
    import jax
    from repro.launch import serve

    t0 = time.perf_counter()
    served = serve.build(args, cfg=cfg, params=params)
    t1 = time.perf_counter()
    done, dt = serve.serve_requests(served, args)
    m = served.router.metrics()
    assert len(done) == args.requests, (len(done), args.requests)
    for r in done:
        assert r.done and len(r.output) == args.max_new, (r.rid, r.output)
        assert all(0 <= t < served.cfg.vocab for t in r.output), r.output
    peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    log(f"  {len(done)} requests x {args.max_new} tokens complete, in-vocab "
        f"(build {t1 - t0:.1f}s, serve incl. compiles {dt:.1f}s)")
    log("  metrics (information): " + json.dumps(
        {k: v for k, v in m.items() if k != "per_engine"}, default=str))
    log(f"  device 0 peak_bytes_in_use (information): {peak}")
    return served, {r.rid: list(r.output) for r in done}


def _first_step(params, cfg, tokens, caches=None, precision=None):
    """Prefill ``tokens[:, :-1]``, then decode ``tokens[:, -1]``.
    Returns [(name, array)]: both logits and every GDN state of the
    first layer group.  ``caches`` default to one chip's."""
    import jax
    from repro.models import lm

    if caches is None:
        caches = lm.init_caches(cfg, tokens.shape[0], 128)
    prefill = jax.jit(lm.prefill, static_argnums=1)
    decode = jax.jit(lm.decode_step, static_argnums=1)
    t0 = time.perf_counter()
    with jax.default_matmul_precision(precision):
        logits_p, caches = prefill(params, cfg, caches, tokens[:, :-1])
        logits_d, caches = decode(params, cfg, tokens[:, -1], caches)
    out = [("prefill logits", logits_p), ("decode logits", logits_d)] + [
        (f"GDN state, pattern position {i}", st)
        for i, st in enumerate(_gdn_states(cfg, caches))]
    jax.block_until_ready(out)
    log(f"  lm.prefill(T={tokens.shape[1] - 1}) + lm.decode_step, "
        f"B={tokens.shape[0]}, {cfg.act_dtype}, "
        f"use_pallas_serving={cfg.use_pallas_serving}, matmul precision "
        f"{precision or 'default'} (incl. compile "
        f"{time.perf_counter() - t0:.1f}s)")
    return out


def _check_path(name, test, base, ref):
    """``test`` and ``base`` (the bf16 one-chip XLA path) against the
    float32 reference ``ref``, output by output."""
    import numpy as np
    for (what, t), (_, b), (_, r) in zip(test, base, ref):
        e_t, e_b = nerr(t, r), nerr(b, r)
        tol = max(PATH_RATIO * e_b, KERNEL_TOL)
        log(f"  {what} vs float32 reference: {name} {e_t:.3e}, one-chip "
            f"XLA {e_b:.3e} (bound {tol:.3e}); {name} vs one-chip XLA "
            f"{nerr(t, b):.3e}")
        assert e_t <= tol, f"{name} {what}: error {e_t} exceeds {tol}"
    same = np.mean(np.argmax(test[1][1], -1) == np.argmax(base[1][1], -1))
    log(f"  decode argmax agreement, {name} vs one-chip XLA "
        f"(information): {same:.2f}")


def _reference_outputs(params, cfg, tokens):
    """The bf16 one-chip XLA path and the float32 reference."""
    return (_first_step(params, cfg, tokens),
            _first_step(params, cfg.replace(act_dtype="float32"), tokens,
                        precision="highest"))


def _gdn_states(cfg, caches):
    return [caches[0][i].S for i, kind in enumerate(cfg.pattern)
            if kind == "gdn"]


def phase_serving():
    import jax
    import numpy as np
    from repro.launch import serve
    from repro.models import lm

    log(f"(c) serving, XLA path: {ARCH} at published width, bf16")
    args = serve.parse_args(_serve_argv())
    served, streams_xla = _serve(args)
    cfg, params = served.cfg, served.params
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"  {n} parameters, use_pallas_serving={cfg.use_pallas_serving}")
    del served                              # free its slot buffers

    log("(d) serving, kernel path (use_pallas_serving=True)")
    cfg_k = cfg.replace(use_pallas_serving=True)
    rng = np.random.default_rng(SEED)
    tokens = jax.numpy.asarray(rng.integers(1, cfg.vocab, (2, 65)),
                               jax.numpy.int32)
    assert _lowers_to_kernel(
        lambda p, t, c: lm.decode_step(p, cfg_k, t, c), params,
        tokens[:, -1], lm.init_caches(cfg_k, 2, 128)), "no kernel on path"
    base, ref = _reference_outputs(params, cfg, tokens)
    _check_path("kernel path", _first_step(params, cfg_k, tokens), base, ref)
    del base, ref

    _, streams_k = _serve(args, cfg=cfg_k, params=params)
    agree = sum(streams_k[r] == streams_xla[r] for r in streams_xla)
    log(f"  token streams identical to the XLA path (information): "
        f"{agree}/{len(streams_xla)}")


def phase_mesh():
    import jax
    import numpy as np
    from repro.launch import serve
    from repro.parallel import sharding as rules
    from repro.models import lm

    log(f"mesh serving: {ARCH} at published width, bf16, vs one chip")
    args1 = serve.parse_args(_serve_argv())
    served1, _ = _serve(args1)
    cfg, params = served1.cfg, served1.params
    del served1
    rng = np.random.default_rng(SEED)
    tokens = jax.numpy.asarray(rng.integers(1, cfg.vocab, (4, 65)),
                               jax.numpy.int32)
    base, ref = _reference_outputs(params, cfg, tokens)
    for mesh in ("4,1", "2,2"):
        log(f"  mesh data,model={mesh}")
        args = serve.parse_args(_serve_argv(**{"--mesh": mesh}))
        served, _ = _serve(args, params=params)
        ex = served.engines[0].executor
        spec = lm.cache_specs(cfg, tokens.shape[0], 128)
        caches = jax.device_put(spec.zeros(), rules.make_shardings(
            ex.mesh, rules.slot_specs(cfg, ex.mesh, spec.shape_dtype(),
                                      tokens.shape[0])))
        _check_path(f"mesh {mesh}",
                    _first_step(ex.params, cfg, tokens, caches), base, ref)
        del served, ex, caches


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded serving path")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    t0 = time.perf_counter()
    device = phase_device(args.chips)
    if args.chips == 4:
        phase_mesh()
    else:
        phase_kernels()
        phase_serving()
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
