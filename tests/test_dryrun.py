"""Dry-run machinery: input_specs, cell lowering, hlo cost extraction, and
the collective parser — on a reduced 8-device mesh in a subprocess (the
512-device production sweep runs via `python -m repro.launch.dryrun`; its
results are validated in EXPERIMENTS.md)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro import configs
from repro.configs.base import SHAPES, shape_applicable
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh


def test_input_specs_all_cells():
    """Every (arch x shape) cell has well-formed ShapeDtypeStruct inputs."""
    for name in configs.ARCHS:
        cfg = configs.get_arch(name)
        for shape_name, shape in SHAPES.items():
            ok, why = shape_applicable(cfg, shape_name)
            if not ok:
                assert "full attention" in why or "quadratic" in why
                continue
            spec = steps_mod.input_specs(cfg, shape)
            leaves = jax.tree.leaves(spec)
            assert leaves, (name, shape_name)
            assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
            if shape.kind == "decode":
                assert spec["tokens"].shape == (shape.global_batch,)
            if cfg.frontend_stub and shape.kind != "decode":
                key = "batch" if shape.kind == "train" else None
                d = spec[key] if key else spec
                assert "embeds" in d          # stub frontend contract


def test_long500k_skips_are_exactly_the_full_attention_archs():
    skips = {n for n in configs.ARCHS
             if not shape_applicable(configs.get_arch(n), "long_500k")[0]}
    assert skips == {"llava-next-34b", "minicpm-2b", "minitron-8b",
                     "yi-9b", "musicgen-medium", "arctic-480b"}


def test_microbatch_sizing():
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = configs.get_arch("arctic-480b")
    mb = steps_mod.microbatches_for(cfg, SHAPES["train_4k"], mesh)
    assert mb >= 1
    # big archs get bf16/factored optimizer state
    ac = steps_mod.adamw_config_for(cfg)
    assert ac.factored and not ac.momentum
    ac_small = steps_mod.adamw_config_for(configs.get_arch("minicpm-2b"))
    assert ac_small.momentum and ac_small.moment_dtype == "float32"


SUB = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["DRYRUN_DIR"] = os.environ.get("TEST_TMP", "/tmp") + "/dr"
    import jax, json
    from repro import configs
    from repro.configs.base import ShapeConfig
    from repro.launch import steps as sm, hlo_cost
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = configs.get_arch("qwen3-next-gdn")
    # small cell: decode against a 2k cache, batch 8
    shape = ShapeConfig("mini_decode", 2048, 8, "decode")
    lowered = sm.lower_cell(cfg, shape, mesh)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    # newer jax drops peak_memory_in_bytes (same compat guard as dryrun.py)
    peak = getattr(mem, "peak_memory_in_bytes", 0) or (
        mem.temp_size_in_bytes + mem.output_size_in_bytes)
    assert peak > 0
    cost = hlo_cost.analyze(compiled.as_text())
    assert cost["bytes"] > 0
    assert cost["flops"] > 0
    print("DRYRUN_SUB_OK", int(cost["bytes"]))
""")


def test_lower_cell_small_mesh_subprocess(tmp_path):
    env = dict(os.environ, PYTHONPATH="src", TEST_TMP=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SUB], capture_output=True,
                       text=True, env=env, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "DRYRUN_SUB_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-4000:]


def test_sweep_results_complete_and_green():
    """The committed production sweep must cover all 88 cells, no errors."""
    d = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "experiments", "dryrun")
    if not os.path.isdir(d):
        pytest.skip("sweep results not present")
    cells = [json.load(open(os.path.join(d, f))) for f in os.listdir(d)
             if f.endswith(".json")]
    assert len(cells) == 88
    assert all(c["status"] in ("ok", "skipped") for c in cells)
    oks = [c for c in cells if c["status"] == "ok"]
    assert len(oks) == 76
    assert all(c["fits_hbm_16g"] for c in oks)
    assert {c["mesh"] for c in oks} == {"single", "multi"}
