"""Sharding rules + multi-device (8 fake CPU devices, subprocess) tests."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch.mesh import make_mesh
from repro.parallel import sharding


def small_mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_fit_spec_drops_and_rebalances():
    mesh = make_mesh((1, 1), ("data", "model"))
    # use a fake 16x16 mesh via axis sizes: emulate with real mesh of 1s —
    # fit_spec only consults axis sizes, so build the spec logic directly.
    # Here sizes are 1 => everything divides; use the 512-device mesh in the
    # subprocess test below for the real thing.
    spec = sharding.fit_spec(P("model", None), (7, 16), mesh)
    assert spec == P("model", None)


def test_param_specs_cover_all_archs():
    mesh = small_mesh()
    for name in configs.ARCHS:
        cfg = configs.get_arch(name).reduced()
        from repro.models import lm
        shapes = jax.eval_shape(lambda k: lm.init_lm(k, cfg),
                                jax.random.PRNGKey(0))
        specs = sharding.params_specs(cfg, shapes, False, mesh)
        flat_sh = jax.tree_util.tree_leaves(shapes)
        flat_sp = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_sh) == len(flat_sp)
        for sh, sp in zip(flat_sh, flat_sp):
            assert len(sp) <= len(sh.shape), (name, sh.shape, sp)


def test_estimate_params_plausible():
    est = sharding.estimate_params(configs.get_arch("yi-9b"))
    assert 8e9 < est < 10e9
    est = sharding.estimate_params(configs.get_arch("arctic-480b"))
    assert 4e11 < est < 5.5e11
    est = sharding.estimate_params(configs.get_arch("mamba2-1.3b"))
    assert 0.9e9 < est < 1.8e9


def test_needs_fsdp_thresholds():
    # force axis sizes via a fake object is overkill — check the math:
    n = sharding.estimate_params(configs.get_arch("arctic-480b"))
    assert n * 14 / 16 > 10e9           # would need fsdp on a 16-way TP


SUBPROCESS_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.configs.base import SHAPES, ShapeConfig
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_mesh
    from repro.runtime import trainer as trainer_mod
    from repro.parallel import sharding

    mesh = make_mesh((4, 2), ("data", "model"))

    # --- 1. a real sharded train step on 8 devices, small shape
    cfg = configs.get_arch("qwen3-next-gdn").reduced()
    shape = ShapeConfig("tiny_train", 64, 8, "train")
    fn, args, in_sh, out_sh, donate = steps_mod.build_cell(cfg, shape, mesh)
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    txt = compiled.as_text()
    assert any(c in txt for c in ("all-reduce", "all-gather")), "no collectives?"

    # --- 2. run it for real: state materialized with the same shardings
    key = jax.random.PRNGKey(0)
    tc = trainer_mod.TrainerConfig(steps=2, seq_len=64, global_batch=8)
    with mesh:
        state = trainer_mod.init_state(key, cfg, tc)
        state = jax.device_put(state, in_sh[0])
        batch = {
            "tokens": jnp.zeros((8, 64), jnp.int32),
            "labels": jnp.zeros((8, 64), jnp.int32),
        }
        batch = jax.device_put(batch, in_sh[1])
        state2, metrics = jitted(state, batch)
        assert np.isfinite(float(metrics["loss"]))

    # --- 3. serve step sharded decode
    dshape = ShapeConfig("tiny_decode", 64, 8, "decode")
    fn, args, in_sh, out_sh, donate = steps_mod.build_cell(cfg, dshape, mesh)
    with mesh:
        co = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate).lower(*args).compile()
    print("SUBPROCESS_OK")
""")


def test_multidevice_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SUBPROCESS_TEST],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=600)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-4000:]
