"""One cell loop at a reduced size on the CPU, called directly: the whole
run but the look for a chip, once for each mixer family of the committed
configurations and under each one's own limit keys.  A sound run is
correct; the float8 control, put in the program's place, is not; and with
the timed path broken underneath (a decode tick that hands back its state
unchanged, a token altered where it is produced) ``correct`` comes out
false."""
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import cell, spec  # noqa: E402

from repro.models import lm  # noqa: E402

SECONDS = 1.0
COMMON = {"vocab": 256, "norm_eps": 1e-6, "tie_embeddings": False,
          "act_dtype": "float32", "state_dtype": "float32"}
# reduced configurations of the program's archs, with the limit keys of
# the committed configuration of each family; at float32 the program
# serves the reference's own argmax, so every limit sits near zero
TINY = {
    "tiny-gdn": ("qwen3-next-gdn", dict(
        COMMON, d_model=64, n_layers=4, pattern=["gdn", "gdn", "gdn", "attn"],
        ffn="dense", d_ff=128,
        mixers={"gdn": {"k_heads": 2, "v_heads": 4, "head_dim": 16},
                "attn": {"heads": 4, "kv_heads": 2, "head_dim": 16,
                         "rope_theta": 10000.0}})),
    "tiny-ssm": ("mamba2-1.3b", dict(
        COMMON, d_model=64, n_layers=1, pattern=["ssm"], ffn="none", d_ff=0,
        mixers={"ssm": {"d_inner": 128, "headdim": 16, "d_state": 32,
                        "conv_width": 4}})),
}
TINY_LIMITS = {"max_logit_gap": 0.01, "mean_logit_gap": 0.001,
               "far_tokens": 0, "served_len_mismatch": 0}


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    """A bench of reduced cells whose files exist only here; the metric
    readers are the benchmark's own."""
    root = tmp_path_factory.mktemp("bench")
    (root / "configs").mkdir()
    (root / "traffic").mkdir()
    (root / "metrics").symlink_to(Path(__file__).resolve().parents[1]
                                  / "metrics")
    for name, (committed, layout) in TINY.items():
        keys = spec.load_config(committed)["harness"]["limits"]
        (root / "configs" / f"{name}.json").write_text(json.dumps({
            "source": "test", "harness": {
                "arch": committed, "full": False, "slots": 4,
                "max_len": 256,
                "check": {"requests": 4, "length": 96, "far_gap": 0.01},
                "layout": layout,
                "limits": {k: TINY_LIMITS[k] for k in keys}}}))
    (root / "traffic" / "tiny_batch.json").write_text(json.dumps({
        "kind": "closed_loop", "clients_per_slot": 2, "warm_s": 1.0,
        "prompt": {"dist": "loguniform", "min": 4, "max": 60},
        "output": {"dist": "loguniform", "min": 10, "max": 24},
        "first_wave_output": {"dist": "uniform", "min": 4, "max": 24},
        "sampling": {"temperature": 0.7, "top_k": 0, "top_p": 0.9,
                     "greedy_share": 0.5}}))
    return root


BENCH = {
    "configs": [{"name": n} for n in TINY],
    "workloads": [{"name": n.replace("tiny-", "batch-"), "config": n,
                   "traffic": "tiny_batch", "chips": 1} for n in TINY],
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "output_tokens_per_s", "unit": "tokens/s"}],
    "per_layer": [{"name": "active_slots.tps", "unit": "%"},
                  {"name": "decode_roofline.tps", "unit": "%"},
                  {"name": "mfu.tps", "unit": "%"},
                  {"name": "idle.tps", "unit": "%"}]}
CELLS = [w["name"] for w in BENCH["workloads"]]


def _run(base, cell_name, seed, trace=False, control=False):
    return cell.run_cell(BENCH, cell_name, seed, SECONDS, trace,
                         t_start=time.perf_counter(), base=base,
                         require_tpu=False, use_cache=False,
                         control=control)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(base, name):
    result, lines, extra = _run(base, name, 2 ** 31 + 5)
    assert result["correct"], lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "output_tokens_per_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert lines[-1].startswith("check served_len_mismatch")
    r = extra["readings"]["program"]
    assert r["tokens"] > 0 and r["gap"] <= 0.01 and r["far_tokens"] == 0
    json.dumps(result)


@pytest.mark.parametrize("name", CELLS)
def test_the_control_in_the_programs_place_is_not_correct(base, name):
    """The float8 control goes through the same numbers and limits as the
    program, and fails them."""
    result, lines, extra = _run(base, name, 2 ** 31 + 5, control=True)
    assert not result["correct"], lines
    checks = result["checks"]
    assert set(checks) == set(spec.load_config(
        TINY[name.replace("batch-", "tiny-")][0])["harness"]["limits"])
    assert any(c["value"] > c["limit"] for c in checks.values())
    r = extra["readings"]
    assert r["control"]["tokens"] == r["program"]["tokens"] > 0
    assert r["control"]["gap"] > 3 * max(r["program"]["gap"], 0.01)
    assert r["control"]["far_tokens"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_per_layer_metrics(base, name):
    result, lines, _ = _run(base, name, 17, trace=True)
    assert result["correct"], lines
    m = result["metrics"]
    # the CPU run has no device plane: the device metrics are left out
    assert "idle.tps" not in m and "decode_roofline.tps" not in m
    assert "mfu.tps" not in m
    assert 0 < m["active_slots.tps"]["value"] <= 100
    assert result["device"]["window_s"] > 0


def _stale_state(orig):
    def decode_steps(params, cfg, tokens, caches, k, **kw):
        toks, valid, last, _, sampler = orig(params, cfg, tokens, caches, k,
                                             **kw)
        return toks, valid, last, caches, sampler
    return decode_steps


def _altered_token(orig):
    def decode_steps(params, cfg, tokens, caches, k, **kw):
        toks, valid, last, caches, sampler = orig(params, cfg, tokens,
                                                  caches, k, **kw)
        toks = toks.at[0].set((toks[0] + 1) % cfg.vocab)
        return toks, valid, last, caches, sampler
    return decode_steps


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_stale_state, _altered_token])
def test_broken_timed_path_is_not_correct(base, monkeypatch, fault, name):
    monkeypatch.setattr(lm, "decode_steps", fault(lm.decode_steps))
    result, lines, extra = _run(base, name, 23)
    assert not result["correct"], lines
    assert extra["readings"]["program"]["far_tokens"] > 0


def test_served_length_mismatch_is_not_correct(base, monkeypatch):
    """A request that served fewer tokens than it asked for fails the
    count check even where every served token is right."""
    real_choose = cell.correct_mod.choose

    def choose(finished, n, rng):
        finished[0][1].pop()
        return real_choose(finished, n, rng)

    monkeypatch.setattr(cell.correct_mod, "choose", choose)
    result, lines, _ = _run(base, CELLS[0], 29)
    assert not result["correct"]
    assert result["checks"]["served_len_mismatch"]["value"] >= 1


def test_a_cpu_run_is_refused():
    with pytest.raises(SystemExit) as e:
        cell.check_device(1, require_tpu=True)
    assert e.value.code == 3
    assert jnp.zeros(1).devices().pop().platform == "cpu"
    assert jax.devices()[0].platform == "cpu"
