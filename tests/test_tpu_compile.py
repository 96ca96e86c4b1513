"""The serving kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) runs the kernel bodies in
Python and never sees the TPU lowering's rules: block shapes whose last
two dims are not (8, 128)-aligned or equal to the array's, scalar
loads, VMEM limits.  These tests compile each kernel with the TPU
compiler for a described, unattached ``v5e:2x2`` chip — no device runs
anything — at the widths of qwen3-next-gdn (GDN B=4, Hk=16, Hv=32,
d=128; attention Hq=16, Hkv=2, d=128) and mamba2-1.3b (the
``delta_rule=False`` path: Hk=1, Hv=64, d_k=128, d_v=64).  The last
test compiles mamba2-1.3b's whole decode tick and reads the layout the
compiler gave its SSD state, and the decode ticks of qwen3-next-gdn and
mamba2-1.3b are compared with the text the compiler gave them before the
``qnext_*`` mixer kinds shared their code.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers all
import this file.
"""
import hashlib
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.attn_decode import attn_decode_pallas
from repro.kernels.gdn_decode import gdn_decode_pallas
from repro.kernels.gdn_prefill import gdn_prefill_pallas
from repro.models import lm
from repro.serving import sampling

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

QWEN = dict(Hk=16, Hv=32, dk=128, dv=128, delta_rule=True)
MAMBA = dict(Hk=1, Hv=64, dk=128, dv=64, delta_rule=False)


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-topology compile is written to the persistent cache
    but cannot be read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    print(compiled.memory_analysis())
    return compiled


@pytest.mark.parametrize("widths,head_block", [
    (QWEN, 2), (QWEN, 8), (QWEN, 32), (MAMBA, 8)])
def test_gdn_decode_compiles(one_chip, widths, head_block):
    B, Hk, Hv, dk, dv = 4, widths["Hk"], widths["Hv"], widths["dk"], \
        widths["dv"]
    _compile(lambda q, k, v, S, g, b: gdn_decode_pallas(
        q, k, v, S, g, b, head_block=head_block,
        delta_rule=widths["delta_rule"]), one_chip,
        ((B, Hk, dk), BF16), ((B, Hk, dk), BF16), ((B, Hv, dv), BF16),
        ((B, Hv, dk, dv), F32), ((B, Hv), F32), ((B, Hv), F32))


@pytest.mark.parametrize("widths,T,chunk,ragged", [
    (QWEN, 128, 64, False), (QWEN, 128, 64, True),
    (QWEN, 16, 16, True),              # the serving engine's prefill chunk
    (MAMBA, 128, 64, True)])
def test_gdn_prefill_compiles(one_chip, widths, T, chunk, ragged):
    BH = 2 * widths["Hv"]
    dk, dv = widths["dk"], widths["dv"]
    shapes = [((BH, T, dk), BF16), ((BH, T, dk), BF16), ((BH, T, dv), BF16),
              ((BH, T), F32), ((BH, T), F32), ((BH, dk, dv), F32)]
    if ragged:
        shapes.append(((BH,), I32))
    _compile(lambda *a: gdn_prefill_pallas(
        *a, chunk=chunk, delta_rule=widths["delta_rule"]), one_chip,
        *shapes)


@pytest.mark.parametrize("window", [None, 512])
def test_attn_decode_compiles(one_chip, window):
    B, Hq, Hkv, T, d = 4, 16, 2, 1024, 128
    _compile(lambda q, k, v, n: attn_decode_pallas(
        q, k, v, n, block_t=256, window=window), one_chip,
        ((B, Hq, d), BF16), ((B, Hkv, T, d), BF16), ((B, Hkv, T, d), BF16),
        ((B,), I32))


def _f32_results(hlo: str, op: str, dims):
    """(name, shape, layout) of every ``op`` instruction whose f32 result
    holds ``dims`` in any order."""
    pat = re.compile(r"%(\S+) = f32\[([\d,]+)\]\{([\d,]+)[^}]*\} "
                     + re.escape(op) + r"\(")
    out = []
    for name, shape, layout in pat.findall(hlo):
        shape = [int(d) for d in shape.split(",")]
        if sorted(shape) == sorted(dims):
            out.append((name, shape, [int(d) for d in layout.split(",")]))
    return out


def test_mamba2_decode_state_layout(one_chip):
    """The decode tick (k=4, 32 slots, caches and sampler donated) keeps
    the SSD state in its stored layout, with d_state = 128 on the lanes:
    no whole-state transpose copy, every fusion writing the state has 128
    minor, and no temporary of the state's size."""
    cfg = configs.get_arch("mamba2-1.3b").replace(n_layers=2)
    slots, max_len, k = 32, 2048, 4

    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = spec(jax.eval_shape(lambda key: lm.init_lm(key, cfg),
                                 jax.random.PRNGKey(0)))
    caches = spec(jax.eval_shape(lambda: lm.init_caches(cfg, slots,
                                                        max_len)))
    sampler = spec(jax.eval_shape(lambda: sampling.init_state(slots)))
    toks = jax.ShapeDtypeStruct((slots,), I32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, t, c, s: lm.decode_steps(p, cfg, t, c, k, sampler=s,
                                           sample_fn=sampling.sample),
        donate_argnums=(2, 3)).lower(params, toks, caches,
                                     sampler).compile()
    hlo = compiled.as_text()
    S = caches[0][0].S
    assert S.dtype == F32 and S.shape[-1] == cfg.ssm_d_state == 128

    assert _f32_results(hlo, "copy", S.shape) == []
    writes = _f32_results(hlo, "fusion", S.shape)
    assert writes, "no fusion writes the SSD state"
    for name, shape, layout in writes:
        assert shape[layout[0]] == 128, (name, shape, layout)
    state_bytes = S.size * S.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < state_bytes / 10


# sha256 of the normalized decode-tick text (``_decode_text``), compiled
# at the parent of the change that added the ``qwen3-next-80b-a3b`` kinds
DECODE_TEXT = Path(__file__).parent / "golden" / "decode_text.json"


def _normalized(hlo: str) -> str:
    """The compiled text less what names and locates it: the tables of
    file names and stack frames, each instruction's ``metadata``, and the
    numbers the compiler appends to instruction names."""
    lines = hlo.splitlines()
    start = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    text = "\n".join(lines[:1] + lines[start:])
    text = re.sub(r",?\s*metadata=\{[^{}]*\}", "", text)
    return re.sub(r"(%[A-Za-z_][\w\-]*?)\.\d+\b", r"\1", text)


def _decode_text(sharding, arch, layers, slots, max_len, k=4):
    cfg = configs.get_arch(arch).replace(n_layers=layers)

    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    params = spec(jax.eval_shape(lambda key: lm.init_lm(key, cfg),
                                 jax.random.PRNGKey(0)))
    caches = spec(jax.eval_shape(lambda: lm.init_caches(cfg, slots,
                                                        max_len)))
    sampler = spec(jax.eval_shape(lambda: sampling.init_state(slots)))
    toks = jax.ShapeDtypeStruct((slots,), I32, sharding=sharding)
    return jax.jit(
        lambda p, t, c, s: lm.decode_steps(p, cfg, t, c, k, sampler=s,
                                           sample_fn=sampling.sample),
        donate_argnums=(2, 3)).lower(params, toks, caches,
                                     sampler).compile().as_text()


@pytest.mark.parametrize("arch,layers,slots,max_len", [
    ("qwen3-next-gdn", 4, 16, 8192), ("mamba2-1.3b", 2, 32, 2048)])
def test_decode_program_text_unchanged(one_chip, arch, layers, slots,
                                       max_len):
    """One period of each benchmarked arch at its cell's slots: the
    decode tick compiles to the same program as before the Qwen3-Next
    kinds came into the shared GDN, attention and model code."""
    text = _normalized(_decode_text(one_chip, arch, layers, slots, max_len))
    want = json.loads(DECODE_TEXT.read_text())[arch]
    assert hashlib.sha256(text.encode()).hexdigest() == want
