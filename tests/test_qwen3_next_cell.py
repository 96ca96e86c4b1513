"""The benchmark's cell loop on Qwen3-Next-80B-A3B's block at reduced
widths on the CPU, called directly as ``benchmarks/onchip/tests/
test_onchip_cell.py`` calls it for the other families: the whole run but
the look for a chip, under the limit keys of the committed
``qwen3-next-80b-a3b`` configuration.  A sound run is correct; the
float8 control, put in the program's place, is not correct."""
import json
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                       / "benchmarks" / "onchip"))
from harness import cell, spec  # noqa: E402

from repro import configs  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
from test_qwen3_next import ARCH, _layout  # noqa: E402

# at float32 the program serves the reference's own argmax: every limit
# sits near zero (as test_onchip_cell.py's)
LIMITS = {"max_logit_gap": 0.01, "mean_logit_gap": 0.001, "far_tokens": 0,
          "served_len_mismatch": 0}
BENCH = {
    "configs": [{"name": "tiny-qnext"}],
    "workloads": [{"name": "batch-qnext", "config": "tiny-qnext",
                   "traffic": "tiny_batch", "chips": 1}],
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "output_tokens_per_s", "unit": "tokens/s"}],
    "per_layer": []}


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    (root / "configs").mkdir()
    (root / "traffic").mkdir()
    (root / "metrics").symlink_to(spec.HERE / "metrics")
    keys = spec.load_config(ARCH)["harness"]["limits"]
    (root / "configs" / "tiny-qnext.json").write_text(json.dumps({
        "source": "test", "harness": {
            "arch": ARCH, "full": False, "slots": 4, "max_len": 256,
            "check": {"requests": 4, "length": 96, "far_gap": 0.01},
            "layout": _layout(configs.get_arch(ARCH).reduced()),
            "limits": {k: LIMITS[k] for k in keys}}}))
    (root / "traffic" / "tiny_batch.json").write_text(json.dumps({
        "kind": "closed_loop", "clients_per_slot": 2, "warm_s": 1.0,
        "prompt": {"dist": "loguniform", "min": 4, "max": 60},
        "output": {"dist": "loguniform", "min": 10, "max": 24},
        "first_wave_output": {"dist": "uniform", "min": 4, "max": 24},
        "sampling": {"temperature": 0.7, "top_k": 0, "top_p": 0.9,
                     "greedy_share": 0.5}}))
    return root


def _run(base, seed, control=False):
    return cell.run_cell(BENCH, "batch-qnext", seed, 1.0, False,
                         t_start=time.perf_counter(), base=base,
                         require_tpu=False, use_cache=False,
                         control=control)


def test_sound_run_is_correct(base):
    result, lines, extra = _run(base, 2 ** 31 + 5)
    assert result["correct"], lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "output_tokens_per_s"}
    r = extra["readings"]["program"]
    assert r["tokens"] > 0 and r["gap"] <= 0.01 and r["far_tokens"] == 0


def test_the_control_in_the_programs_place_is_not_correct(base):
    result, lines, extra = _run(base, 2 ** 31 + 5, control=True)
    assert not result["correct"], lines
    r = extra["readings"]
    assert r["control"]["tokens"] == r["program"]["tokens"] > 0
    assert r["control"]["gap"] > 3 * max(r["program"]["gap"], 0.01)
