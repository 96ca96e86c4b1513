"""The serving path's tracing: each program's XLA name, the ``serve:``
host spans a profiler records beside the device's operations, and the
scheduler's tick log, prefill log, self time and compile count."""
import gc
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.models import lm
from repro.serving import executor as executor_mod
from repro.serving import spans
from repro.serving.scheduler import Request, Scheduler

STEP_CHILDREN = {"serve:admit", "serve:decode.dispatch", "serve:decode.sync",
                 "serve:emit"}
ADMIT_CHILDREN = {"serve:scatter", "serve:prefill.dispatch",
                  "serve:prefill.sync"}


@pytest.fixture(scope="module")
def gdn():
    cfg = configs.get_arch("qwen3-next-gdn").reduced()
    return cfg, lm.init_lm(jax.random.PRNGKey(0), cfg)


def _engine(gdn, **kw):
    cfg, params = gdn
    return Scheduler(cfg, params, max_slots=2, max_len=64, decode_block=4,
                     prefill_chunk=8, **kw)


def _requests(base, n=3):
    """Prompts of 11-21 tokens (full chunks and a ragged tail), budgets
    of 5-9 tokens: ticks of length 4, then shorter ones."""
    return [Request(rid=base + i,
                    prompt=np.arange(1, 12 + 5 * i, dtype=np.int32),
                    max_new_tokens=5 + 2 * i) for i in range(n)]


def _serve(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)


def _host_spans(data):
    """(start_ns, end_ns, name, stats) of every ``serve:`` host event."""
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
                           for ev in line.events
                           if ev.name.startswith(spans.PREFIX))
    return sorted(out)


def _inside(child, parents):
    """The innermost of ``parents`` that holds ``child``."""
    s, e = child[0], child[1]
    held = [p for p in parents if p[0] <= s and e <= p[1]]
    return min(held, key=lambda p: p[1] - p[0]) if held else None


@pytest.fixture(scope="module")
def traced(gdn, tmp_path_factory):
    """A warmed engine serves three requests under the profiler; the
    decode results it returned are kept beside the trace."""
    eng = _engine(gdn)
    _serve(eng, _requests(0))
    eng.reset_metrics()
    decoded = []
    orig = eng.executor.decode

    def decode(k):
        ctx = {s: r.prompt_len + len(r.output)
               for s, r in eng.active.items()}
        toks, valid = orig(k)
        decoded.append((k, valid, ctx))
        return toks, valid
    eng.executor.decode = decode
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        _serve(eng, _requests(100))
        gc.collect()
    eng.executor.decode = orig
    [path] = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    return eng.metrics(), decoded, _host_spans(ProfileData.from_file(path))


def test_spans_nest_as_the_step_runs(traced):
    _, _, ev = traced
    names = {e[2] for e in ev}
    assert STEP_CHILDREN | ADMIT_CHILDREN | {"serve:step",
                                            "serve:gc"} <= names
    steps = [e for e in ev if e[2] == "serve:step"]
    admits = [e for e in ev if e[2] == "serve:admit"]
    for e in ev:
        if e[2] in STEP_CHILDREN:
            parent = _inside(e, steps)
            assert parent is not None and \
                parent[3]["tick"] == e[3]["tick"], e
        elif e[2] in ADMIT_CHILDREN:
            parent = _inside(e, admits)
            assert parent is not None and \
                parent[3]["tick"] == e[3]["tick"], e
    # each tick's phases run in order: admit, dispatch, sync, emit
    for step in steps:
        kids = [e[2] for e in ev if e[2] in STEP_CHILDREN
                and _inside(e, steps) is step]
        assert kids in ([], ["serve:admit"], ["serve:admit",
                                              "serve:decode.dispatch",
                                              "serve:decode.sync",
                                              "serve:emit"]), kids


def test_span_ticks_match_the_logs(traced):
    m, _, ev = traced
    assert [e[3]["tick"] for e in ev if e[2] == "serve:decode.dispatch"] \
        == [t["tick"] for t in m["tick_log"]]
    assert [(e[3]["tick"], e[3]["program"]) for e in ev
            if e[2] == "serve:prefill.dispatch"] \
        == [(p["tick"], p["program"]) for p in m["prefill_log"]]
    assert m["steps"] == sum(1 for e in ev if e[2] == "serve:step")
    assert m["ticks"] == len(m["tick_log"])


def test_tick_log_counts_the_live_slots_of_each_step(traced):
    m, decoded, _ = traced
    assert len(decoded) == len(m["tick_log"])
    for (k, valid, ctx), t in zip(decoded, m["tick_log"]):
        assert t["k"] == k == valid.shape[0]
        assert t["live"] == [int(n) for n in valid.sum(axis=1)]
        assert t["ctx"] == [sum(c + j for s, c in ctx.items()
                                if valid[j, s]) for j in range(k)]


def test_prefill_log_rows_cover_each_prompt(traced):
    m, _, _ = traced
    covered = {}
    for p in m["prefill_log"]:
        assert p["program"] in ("prefill_scan", "admit")
        for rid, start, valid in p["rows"]:
            assert start == covered.get(rid, 0) and valid > 0
            covered[rid] = start + valid
    assert covered == {r.rid: r.prompt_len for r in _requests(100)}
    admits = [rid for p in m["prefill_log"] if p["program"] == "admit"
              for rid, _, _ in p["rows"]]
    assert sorted(admits) == [100, 101, 102]


def test_self_time_leaves_out_the_waits(traced):
    m, _, ev = traced
    step_s = sum(e[1] - e[0] for e in ev if e[2] == "serve:step") * 1e-9
    sync_s = sum(e[1] - e[0] for e in ev
                 if e[2].endswith(".sync")) * 1e-9
    assert 0 < m["sched_self_s"] < step_s
    # the scheduler times the same phases the spans mark
    assert m["sched_self_s"] == pytest.approx(step_s - sync_s, rel=0.2,
                                              abs=2e-3)


def test_a_new_tick_length_counts_one_compile(gdn):
    eng = _engine(gdn)
    _serve(eng, [Request(rid=1, prompt=np.arange(1, 12, dtype=np.int32),
                         max_new_tokens=5)])
    assert eng.metrics()["compiles"] > 0        # first use of each program
    eng.reset_metrics()
    _serve(eng, [Request(rid=2, prompt=np.arange(1, 12, dtype=np.int32),
                         max_new_tokens=5)])
    assert eng.metrics()["compiles"] == 0       # every program reused
    eng.reset_metrics()
    # a budget of 3: the admit token, then one tick of length 2
    _serve(eng, [Request(rid=3, prompt=np.arange(1, 12, dtype=np.int32),
                         max_new_tokens=3)])
    m = eng.metrics()
    assert [t["k"] for t in m["tick_log"]] == [2]
    assert m["compiles"] == 1


def test_every_program_is_named_for_its_family(gdn, monkeypatch):
    """Each program's lowered module is ``jit_serve_<family>``; every
    tick length of the decode family shares the name."""
    first = {}
    call = executor_mod._Program.__call__

    def record(self, *args):
        first.setdefault(id(self), (self, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args)))
        return call(self, *args)
    monkeypatch.setattr(executor_mod._Program, "__call__", record)
    for kw in ({}, {"prefill_batching": False, "plan_mode": "pow2"}):
        eng = _engine(gdn, **kw)
        _serve(eng, _requests(0))
    families = {}
    for prog, specs in first.values():
        head = prog.fn.lower(*specs).as_text().split("\n", 1)[0]
        assert head.startswith(f"module @jit_{prog.name} "), head
        families.setdefault(prog.name, 0)
        families[prog.name] += 1
    assert {"serve_decode", "serve_prefill_scan", "serve_prefill_chunk",
            "serve_admit", "serve_scatter", "serve_staging_zeros"} \
        <= set(families)
    assert families["serve_decode"] >= 2        # lengths 4, 2 and 1
