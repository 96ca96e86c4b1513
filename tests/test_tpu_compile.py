"""The serving kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) runs the kernel bodies in
Python and never sees the TPU lowering's rules: block shapes whose last
two dims are not (8, 128)-aligned or equal to the array's, scalar
loads, VMEM limits.  These tests compile each kernel with the TPU
compiler for a described, unattached ``v5e:2x2`` chip — no device runs
anything — at the widths of qwen3-next-gdn (GDN B=4, Hk=16, Hv=32,
d=128; attention Hq=16, Hkv=2, d=128) and mamba2-1.3b (the
``delta_rule=False`` path: Hk=1, Hv=64, d_k=128, d_v=64).  The last
tests compile whole decode ticks: mamba2-1.3b's, for the layout the
compiler gave its SSD state; qwen3-next-gdn's and mamba2-1.3b's, against
the text recorded in ``golden/decode_text.json``; and both qwen3-next
archs', for stacked caches updated in place (no whole-cache copy in a
loop, one K/V row written per slot).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers all
import this file.
"""
import hashlib
import json
import math
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


def _decode_tick(sharding, arch, layers, slots, max_len, k=4, count=False):
    """The serving decode tick (``decode_steps``, caches and sampler
    donated) of ``arch`` at ``layers`` layers, compiled, and the shapes
    of its cache leaves."""
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
    compiled = jax.jit(
        lambda p, t, c, s: lm.decode_steps(p, cfg, t, c, k, sampler=s,
                                           sample_fn=sampling.sample,
                                           count=count),
        donate_argnums=(2, 3)).lower(params, toks, caches,
                                     sampler).compile()
    return compiled, caches


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
    cfg = configs.get_arch("mamba2-1.3b")
    compiled, caches = _decode_tick(one_chip, "mamba2-1.3b", 2, 32, 2048)
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
# when the decode tick came to update its stacked caches in place
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
    return _decode_tick(sharding, arch, layers, slots, max_len,
                        k)[0].as_text()


@pytest.mark.parametrize("arch,layers,slots,max_len", [
    ("qwen3-next-gdn", 4, 16, 8192), ("mamba2-1.3b", 2, 32, 2048)])
def test_decode_program_text_unchanged(one_chip, arch, layers, slots,
                                       max_len):
    """One period of each benchmarked arch at its cell's slots: the
    decode tick compiles to the program recorded in the golden, which a
    change to the shared GDN, attention or model code must leave as it
    is, or regenerate (``tests/golden/README.md``)."""
    text = _normalized(_decode_text(one_chip, arch, layers, slots, max_len))
    want = json.loads(DECODE_TEXT.read_text())[arch]
    assert hashlib.sha256(text.encode()).hexdigest() == want


_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\{[^}]*\} "
                    r"([\w\-]+)\((.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")


def _computations(hlo: str):
    """{computation name: its instruction lines}."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.strip() not in ("", "}"):
            cur.append(line)
    return comps


def _instructions(lines):
    """(name, shape, opcode, operand names) of each array instruction."""
    for line in lines:
        m = _INSTR.match(line)
        if m:
            name, shape, op, rest = m.groups()
            yield (name, tuple(int(d) for d in shape.split(",") if d), op,
                   re.findall(r"%([\w.\-]+)", rest.split(")")[0]))


def _in_loops(comps):
    """The names of every while-loop body and what it calls, nested loop
    bodies and fused computations included."""
    todo = [b for lines in comps.values() for line in lines
            for b in re.findall(r"\bbody=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(
                r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", line)
            for group in re.findall(r"called_computations=\{([^}]*)\}",
                                    line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return seen


@pytest.mark.parametrize("arch,layers,slots,max_len,count,parent_temp", [
    ("qwen3-next-gdn", 8, 16, 8192, False, 1_042_673_664),
    ("qwen3-next-80b-a3b", 16, 16, 4096, True, 1_014_640_640)])
def test_decode_updates_caches_in_place(one_chip, arch, layers, slots,
                                        max_len, count, parent_temp):
    """Two periods of each qwen3-next arch at its cell's slots, the decode
    tick as the executor serves it: the stacked caches are carried
    through the layer loop and updated in place.  No copy inside any loop
    has a stacked cache leaf's shape (a scanned ``ys`` copied back into
    the step loop's carry did, once per leaf per step); every write into
    a stacked K or V leaf has a row per slot as its update (not a whole
    layer); and the temporaries are under half of what the ``xs``/``ys``
    layer scan needed (``parent_temp``, compiled the same way)."""
    compiled, _ = _decode_tick(one_chip, arch, layers, slots, max_len,
                               count=count)
    cfg = configs.get_arch(arch).replace(n_layers=layers)
    spec = lm.cache_specs(cfg, slots, max_len).leaves()
    stacked = {tuple(sorted(a.shape)) for a in spec if a.role != "meta"}
    kv = [a.shape for a in spec if a.role == "window"]
    assert kv, "no stacked K or V leaf"
    comps = _computations(compiled.as_text())
    loops = _in_loops(comps)
    assert loops, "the decode tick has no loop"
    shapes = {name: shape for lines in comps.values()
              for name, shape, _, _ in _instructions(lines)}
    writes = 0
    for c, lines in comps.items():
        for name, shape, op, operands in _instructions(lines):
            if op == "copy" and c in loops:
                assert tuple(sorted(shape)) not in stacked, (c, name, shape)
            if op in ("dynamic-update-slice", "scatter") and shape in kv:
                # (layers, slots, Hkv, T, hd): a row is (slots, Hkv, hd)
                row = shape[1] * shape[2] * shape[4]
                update = operands[1 if op == "dynamic-update-slice" else -1]
                assert math.prod(shapes[update]) <= row, (
                    name, shape, shapes[update])
                writes += 1
    assert writes >= len(kv), "K and V are not written in the tick"
    assert compiled.memory_analysis().temp_size_in_bytes < parent_temp / 2
