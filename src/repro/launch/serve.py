"""Serving launcher: continuous-batching decode with persistent state slots.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b \
        --requests 8 --max-new 16 --decode-block 4 --temperature 0.8 \
        --top-k 40 --top-p 0.95

``--decode-block k`` fuses k decode+sample steps per engine tick on device
(one host sync per k tokens); sampling runs on device with per-slot
temperature / top-k / top-p.  Prefill is chunked (``--prefill-chunk``) and
by default overlapped: queued requests stream into a ring of
``--staging-depth`` staging buffers at tick boundaries while resident
slots decode, with the first token sampled on device by the fused admit
head (``--serialized`` restores the prefill-behind-a-free-slot baseline;
token streams are bitwise identical).

``--mesh DATA,MODEL`` runs each engine mesh-sharded: the slot axis is
data-parallel over DATA devices (``--slots`` is padded up to a multiple)
and the recurrent-state heads / KV context are sharded over MODEL devices
(the paper's head-parallelism axis scaled out); every tick stays one SPMD
program.  ``--engines N`` fronts N such engines with a host-side router
(``--router-policy``), each engine on its own slice of the visible
devices when enough exist.  On CPU, prefix
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to smoke-test a
topology.

``--swap-policy``/``--idle-swap-ms``/``--max-live-requests`` turn on
slot oversubscription (state paging): idle or outranked active requests
are swapped — fixed-size recurrent state + rolling KV window + sampler
row, straight from ``cache_spec`` — to host memory and resumed later
through the same slot-scatter program, bitwise-identically.  See
docs/serving.md.

``--speculative [--draft-config NAME] [--k-draft K]`` turns on
draft-verify speculative decode inside the device-resident tick: the
draft proposes K tokens per slot, one fused verify program scores them
against the target with the same per-slot sampler keys, and rejected
positions roll the recurrent state back through a per-slot checkpoint
buffer — token streams stay bitwise identical to non-speculative
decode while each accepted run costs one host sync.
``--adaptive-k-draft`` lets a windowed acceptance rate shrink/grow the
effective draft length within [1, K] — a bad draft collapses to
verify-heavy k=1 ticks instead of burning K rejected proposals per sync.

``--rpc`` puts each engine in its own worker process
(``repro.serving.rpc.EngineProxy`` over a framed pipe protocol);
``--workers N`` is shorthand for ``--rpc --engines N``.  ``--roles``
assigns per-engine roles for disaggregated serving, cycled over the
engines (e.g. ``--roles prefill,decode``): prefill engines pause every
request at the admit boundary and the router ships the swapped image to
the least-loaded compatible decode engine — decode ticks never share an
engine with prefill work, streams stay bitwise the colocated ones.  The
launcher process then never initialises JAX's backend: each worker
builds its weights from ``--seed`` and, on a TPU host, owns one chip
(more workers than chips is an error).

``--full`` serves the architecture at its published widths (default:
the reduced CPU smoke config).  Compiled programs persist in
``$JAX_COMPILATION_CACHE_DIR``, or in ``<checkout>/.jax_cache`` when it
is unset (``repro.launch.compile_cache``).

Programmatic callers (``chip_smoke.py``) use the same pieces:
``parse_args`` → ``build`` → ``serve_requests``.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any, List, Optional

import jax
import numpy as np

from repro import configs
from repro.configs.base import ArchConfig, ServingTopology
from repro.launch import compile_cache
from repro.launch import mesh as mesh_mod
from repro.models import lm
from repro.serving.engine import DecodeEngine, EngineProxy, Request, Router
from repro.serving.rpc import worker_chips


def _roles(args):
    """Per-engine roles, cycled over ``--roles`` (default: every engine
    serves both prefill and decode)."""
    roles = [r.strip() for r in (args.roles or "both").split(",")]
    for r in roles:
        if r not in ("prefill", "decode", "both"):
            raise SystemExit(f"--roles: unknown role {r!r} "
                             f"(prefill/decode/both)")
    return [roles[i % len(roles)] for i in range(args.engines)]


def build_engines(cfg, params, args, topo: ServingTopology):
    """One engine per ``--engines``, each on its own consecutive device
    slice when the backend has enough devices (otherwise they share the
    first slice — correct, just not physically parallel).  With
    ``--rpc`` each engine is an ``EngineProxy`` worker process instead
    (its own interpreter and jax runtime — real process parallelism);
    weights ship as the init seed, rebuilt bitwise-identically by each
    worker, and ``params`` is unused."""
    slots = topo.pad_slots(args.slots)
    if slots != args.slots:
        print(f"slots padded {args.slots} -> {slots} "
              f"(multiple of data={topo.data})")
    roles = _roles(args)
    common = dict(
        max_slots=slots, max_len=args.max_len,
        seed=args.seed, decode_block=args.decode_block,
        overlap=args.overlap, prefill_chunk=args.prefill_chunk,
        budget_ticks=args.budget_ticks,
        staging_depth=topo.staging_depth,
        plan_mode=args.plan_mode,
        prefill_batching=args.prefill_batching,
        prefill_budget=args.prefill_budget,
        swap_policy=args.swap_policy,
        idle_swap_ms=args.idle_swap_ms,
        max_live_requests=args.max_live_requests,
        async_paging=args.async_paging,
        gather_ring=args.gather_ring,
        host_swap_bytes=args.host_swap_bytes,
        swap_spool_dir=args.swap_spool_dir,
        speculative=args.speculative,
        draft_cfg=_draft_cfg(cfg, args),
        k_draft=args.k_draft,
        adaptive_k=args.adaptive_k)
    engines = []
    dm = topo.devices
    if args.rpc:
        chips = worker_chips(args.engines)
        if dm > 1 and chips[0] is not None:
            raise SystemExit("--rpc on a TPU host runs one chip per worker; "
                             "serve a --mesh in process instead")
        mesh_shape = None if dm == 1 else topo.shape
        draft_seed = args.seed + 1 if common["draft_cfg"] else None
        for i in range(args.engines):
            print(f"spawning worker {i} (role={roles[i]}"
                  + (f", chip {chips[i]}" if chips[i] is not None else "")
                  + ")...")
            engines.append(EngineProxy(
                cfg, params_seed=args.seed, draft_params_seed=draft_seed,
                chip=chips[i], role=roles[i], mesh_shape=mesh_shape,
                mesh_axes=topo.axes if mesh_shape else None, **common))
        return engines, slots
    if common["draft_cfg"] is not None:
        common["draft_params"] = lm.init_lm(
            jax.random.PRNGKey(args.seed + 1), common["draft_cfg"])
    devs = jax.devices()
    shared_note = False
    for i in range(args.engines):
        lo = i * dm
        if lo + dm <= len(devs):
            sl = devs[lo:lo + dm]
        else:
            sl = devs[:dm]
            if not shared_note:
                shared_note = True
                print(f"note: engines {i}..{args.engines - 1} share "
                      f"devices 0..{dm - 1} with engine 0 (only "
                      f"{len(devs)} visible) — correct, but they "
                      f"time-slice the same hardware")
        mesh = (None if dm == 1 and args.engines == 1 else
                mesh_mod.make_mesh(topo.shape, topo.axes, devices=sl))
        engines.append(DecodeEngine(cfg, params, mesh=mesh,
                                    role=roles[i], **common))
    return engines, slots


def _draft_cfg(cfg: ArchConfig, args) -> Optional[ArchConfig]:
    """The speculative draft's config (``None``: none, or self-draft)."""
    if not args.speculative or args.draft_config == "self":
        return None
    dcfg = configs.get_arch(args.draft_config)
    if args.reduced:
        dcfg = dcfg.reduced()
    if dcfg.vocab != cfg.vocab:
        raise SystemExit(f"--draft-config {args.draft_config}: vocab "
                         f"{dcfg.vocab} != target vocab {cfg.vocab}")
    return dcfg


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--decode-block", type=int, default=4,
                    help="decode+sample steps fused per engine tick "
                         "(host syncs once per block)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt chunk size for staged prefill")
    ap.add_argument("--plan-mode", default="masked",
                    choices=("masked", "pow2"),
                    help="prefill chunk planning: 'masked' (default) "
                         "dispatches one scan shape + one fixed-size "
                         "valid_len-masked tail per prompt (O(1) compile "
                         "cache); 'pow2' keeps the power-of-two tail "
                         "decomposition as the comparison baseline")
    ap.add_argument("--mesh", default="1,1",
                    help="engine mesh topology DATA,MODEL (slot axis on "
                         "data, state heads / KV context on model); "
                         "slots are padded to a multiple of DATA")
    ap.add_argument("--staging-depth", type=int, default=2,
                    help="staging-buffer ring size: ahead-of-slot "
                         "prefills outstanding under saturation")
    ap.add_argument("--no-prefill-batching", dest="prefill_batching",
                    action="store_false", default=None,
                    help="dispatch one prefill program per staged prompt "
                         "instead of fusing all staged prompts into one "
                         "batched fixed-shape program per tick (the "
                         "default batches whenever every mixer kind "
                         "supports per-row masks and the FFN is not MoE)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="per-tick prefill token budget of the batched "
                         "packer under saturation (default: every "
                         "staging row gets a full scan + admit)")
    ap.add_argument("--swap-policy", default="manual",
                    choices=("manual", "idle", "pressure", "auto"),
                    help="slot-oversubscription eviction policy: "
                         "'manual' (pause/resume/preempt API only), "
                         "'idle' (swap out active requests whose "
                         "activity lease exceeds --idle-swap-ms; touch() "
                         "renews the lease), 'pressure' (evict the "
                         "lowest-priority active request when a strictly "
                         "higher-priority request waits without a free "
                         "slot), 'auto' (both)")
    ap.add_argument("--idle-swap-ms", type=float, default=None,
                    help="activity-lease duration for --swap-policy "
                         "idle/auto: an active request untouched this "
                         "long is swapped to host, freeing its slot")
    ap.add_argument("--max-live-requests", type=int, default=None,
                    help="admission cap on LIVE sessions (queued + "
                         "staging + active + swapped) per engine — "
                         "oversubscription bounds host memory, not just "
                         "device slots (default: unlimited)")
    ap.add_argument("--async-paging", action="store_true", default=False,
                    help="overlap swap transfers with the decode tick: "
                         "swap-outs drain D2H in the background through "
                         "a ring of gather buffers (harvested at tick "
                         "boundaries) and predictable resume grants "
                         "prestage their H2D put one tick ahead — "
                         "streams stay bitwise-identical to synchronous "
                         "paging")
    ap.add_argument("--gather-ring", type=int, default=2,
                    help="device-side gather buffers for async paging: "
                         "how many swap-out drains may be outstanding "
                         "before a dispatch force-harvests the oldest "
                         "(default 2 — double buffering)")
    ap.add_argument("--host-swap-bytes", type=int, default=None,
                    help="spill watermark: when in-memory swapped images "
                         "exceed this many bytes, the coldest dormant "
                         "one spills to --swap-spool-dir (default: no "
                         "spilling unless a spool dir is set, then 0 — "
                         "spill every dormant image)")
    ap.add_argument("--swap-spool-dir", default=None,
                    help="directory for spilled swap images (wire codec) "
                         "(spill-to-disk tier for truly cold sessions; "
                         "images reload transparently on resume)")
    ap.add_argument("--engines", type=int, default=1,
                    help="number of per-mesh engines behind the router")
    ap.add_argument("--rpc", action="store_true", default=False,
                    help="run each engine in its own worker process "
                         "(EngineWorker subprocess behind an "
                         "EngineProxy) instead of in-process")
    ap.add_argument("--workers", type=int, default=None,
                    help="shorthand for --rpc --engines N")
    ap.add_argument("--roles", default=None,
                    help="comma list of per-engine roles cycled over the "
                         "engines, e.g. 'prefill,decode' for "
                         "disaggregated serving (default: every engine "
                         "is 'both')")
    ap.add_argument("--router-policy", default="least_loaded",
                    choices=("least_loaded", "round_robin"))
    ap.add_argument("--serialized", dest="overlap", action="store_false",
                    default=True,
                    help="disable prefill/decode overlap (admit prefills "
                         "behind a free slot, on the tick thread)")
    ap.add_argument("--no-budget-ticks", dest="budget_ticks",
                    action="store_false", default=True,
                    help="always run full decode-block ticks (disable the "
                         "budget-aware tick-length cap)")
    ap.add_argument("--speculative", action="store_true", default=False,
                    help="draft-verify speculative decode inside the "
                         "device tick: a draft model proposes --k-draft "
                         "tokens per slot, one fused verify program "
                         "scores them with the target and rolls "
                         "recurrent state back to the last accepted "
                         "position; token streams stay bitwise identical "
                         "to non-speculative decode")
    ap.add_argument("--draft-config", default="self",
                    help="draft model for --speculative: 'self' (default; "
                         "the target drafts for itself — acceptance "
                         "upper bound) or any registered arch name with "
                         "the same vocab (randomly initialised here; a "
                         "real deployment loads trained draft weights)")
    ap.add_argument("--k-draft", type=int, default=4,
                    help="draft tokens proposed per slot per "
                         "speculative tick (each tick emits 1..k+1 "
                         "tokens per slot on one host sync)")
    ap.add_argument("--adaptive-k-draft", dest="adaptive_k",
                    action="store_true", default=False,
                    help="acceptance-adaptive draft length: a windowed "
                         "acceptance rate shrinks/grows the effective k "
                         "within [1, --k-draft]; streams unchanged")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="device top-k sampling (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="device nucleus sampling (1.0 = disabled)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="published widths instead of the reduced config")
    args = ap.parse_args(argv)
    if args.workers is not None:
        args.rpc = True
        args.engines = args.workers
    return args


@dataclass
class Served:
    """What ``build`` returns: the router over its engines, plus the
    config and (in-process engines only) the parameters they serve."""
    cfg: ArchConfig
    params: Any
    topo: ServingTopology
    engines: list
    router: Router
    slots: int


def build(args, *, cfg: Optional[ArchConfig] = None,
          params: Any = None) -> Served:
    """Config, weights (from ``--seed``), engines and router for ``args``.

    ``cfg``/``params`` replace the ones ``args`` names, for callers that
    serve one set of weights under several configs.  With ``--rpc`` the
    workers build their own weights and this process never initialises
    JAX's backend.
    """
    topo = ServingTopology.parse(args.mesh,
                                 staging_depth=args.staging_depth)
    if cfg is None:
        cfg = configs.get_arch(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
    if params is None and not args.rpc:
        params = lm.init_lm(jax.random.PRNGKey(args.seed), cfg)
    engines, slots = build_engines(cfg, params, args, topo)
    return Served(cfg, params, topo, engines,
                  Router(engines, policy=args.router_policy), slots)


def serve_requests(served: Served, args):
    """Submit ``--requests`` seeded random prompts (4–16 tokens) and
    serve them to completion.  Returns (finished requests, seconds)."""
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        prompt = rng.integers(1, served.cfg.vocab, size=rng.integers(4, 17),
                              dtype=np.int32)
        served.router.submit(Request(rid=i, prompt=prompt,
                                     max_new_tokens=args.max_new,
                                     temperature=args.temperature,
                                     top_k=args.top_k, top_p=args.top_p))
    t0 = time.perf_counter()
    done = served.router.run_until_done()
    return done, time.perf_counter() - t0


def main(argv: Optional[List[str]] = None):
    compile_cache.enable()
    args = parse_args(argv)
    served = build(args)
    cfg, topo, slots = served.cfg, served.topo, served.slots
    engines, router = served.engines, served.router
    eng = engines[0]
    # per-slot budgets straight from the mixers' declarative cache specs
    print(f"topology: {args.engines} "
          f"{'worker process(es)' if args.rpc else 'engine(s)'} x mesh "
          f"data={topo.data},model={topo.model} "
          f"(staging ring depth {topo.staging_depth}, "
          f"router={args.router_policy}, "
          f"roles={','.join(_roles(args))})")
    if not args.rpc:
        print(f"engine: {slots} slots x "
              f"(persistent state "
              f"{eng.state_bytes_per_slot / 2**10:.1f} KiB"
              f" + window/KV {eng.window_bytes_per_slot / 2**10:.1f} KiB)"
              f" = {eng.cache_bytes / 2**20:.2f} MiB slot buffers, "
              f"decode_block={args.decode_block}, "
              f"prefill={'overlapped' if args.overlap else 'serialized'} "
              f"chunks of {eng.prefill_chunk} ({eng.plan_mode} plans, "
              f"{'batched' if eng.prefill_batching else 'per-prompt'} "
              f"staging)")
    if not args.rpc and (args.swap_policy != "manual"
                         or args.max_live_requests
                         or args.async_paging or args.swap_spool_dir):
        print(f"paging: swap_policy={args.swap_policy}"
              + (f", idle lease {args.idle_swap_ms:.0f} ms"
                 if args.idle_swap_ms is not None else "")
              + (f", max {args.max_live_requests} live sessions/engine"
                 if args.max_live_requests else "")
              + (f", async (gather ring {args.gather_ring})"
                 if args.async_paging else ", synchronous")
              + (f", spool {args.swap_spool_dir} @ "
                 f"{(args.host_swap_bytes or 0) / 2**20:.1f} MiB watermark"
                 if args.swap_spool_dir else "")
              + f" — {eng.executor.swap_bytes_per_slot / 2**10:.1f} "
              f"KiB/swap from cache_spec")
    if args.speculative and not args.rpc:
        ex = eng.executor
        print(f"speculative: draft={args.draft_config}, "
              f"k_draft={args.k_draft} — per slot "
              f"{ex.checkpoint_bytes_per_slot / 2**10:.1f} KiB rollback "
              f"checkpoint + {ex.draft_bytes_per_slot / 2**10:.1f} KiB "
              f"draft state "
              f"({ex.speculative_bytes / 2**20:.2f} MiB total, from "
              f"checkpoint_spec)")
    done, dt = serve_requests(served, args)
    m = router.metrics()
    print(f"served {m['requests']} requests, {m['tokens']} tokens in "
          f"{dt:.2f}s ({m['tokens'] / dt:.1f} tok/s) over "
          f"{m['ticks']} engine ticks "
          f"(placed {m['placed']}, migrated {m['migrated']}"
          + (f", {m['handoffs']} prefill→decode handoffs"
             if m["handoffs"] else "") + ")")
    print(f"  decode: {m['decode_us_per_token']:.0f} us/token "
          f"({m['decoded_tokens']} tokens in {m['decode_s']:.2f}s, "
          f"one host sync per {args.decode_block} tokens, "
          f"{m['stage_dispatches']} staged prefill + "
          f"{m['scatter_dispatches']} scatter dispatches)")
    if args.speculative:
        print(f"  speculative: {m['drafted_tokens']} drafted / "
              f"{m['accepted_tokens']} accepted "
              f"({m['acceptance_rate']:.2f} acceptance), "
              f"{m['spec_ticks']} draft-verify ticks, "
              f"{m['syncs_per_token']:.3f} host syncs/token, "
              f"{m['draft_prefills']} draft-state rebuilds")
    print(f"  per-request means: ttft {m['mean_ttft_s'] * 1e3:.1f} ms, "
          f"latency {m['mean_latency_s'] * 1e3:.1f} ms, "
          f"{m['mean_tokens_per_s']:.1f} tok/s")
    if m["swap_outs"] or m["swapped"]:
        us_mb = (m["swap_s"] * 1e6 / (m["swap_bytes"] / 2**20)
                 if m["swap_bytes"] else 0.0)
        print(f"  paging: {m['swap_outs']} swap-outs / {m['swap_ins']} "
              f"swap-ins, {m['swap_bytes'] / 2**20:.2f} MiB moved "
              f"({us_mb:.0f} us/MiB), {m['swapped']} session(s) parked "
              f"on host at exit")
        print(f"    dispatch {m['swap_dispatch_s'] * 1e3:.2f} ms / stall "
              f"{m['swap_stall_s'] * 1e3:.2f} ms"
              + (f", {m['swap_harvests_overlapped']} overlapped + "
                 f"{m['swap_harvests_forced']} forced harvests, "
                 f"{m['swap_prefetch_hits']}/{m['swap_prefetches']} "
                 f"prefetch hits" if args.async_paging else "")
              + (f", {m['spills']} spills / {m['spill_loads']} reloads "
                 f"({m['spill_bytes'] / 2**20:.2f} MiB spooled)"
                 if args.swap_spool_dir else ""))
    for r in done[:4]:
        print(f"  req {r.rid}: ttft {r.ttft_s * 1e3:.1f} ms, "
              f"{len(r.output)} toks: {list(r.output)}")
    if args.rpc:
        for e in engines:
            e.shutdown()
    return done, m


if __name__ == "__main__":
    main()
