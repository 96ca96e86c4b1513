"""The benchmark's own copies of the FLOP and byte arithmetic agree with
the program's at published widths, and each configuration file describes
the model the program builds."""
import math
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import spec  # noqa: E402
from harness.cost import Cost  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import intensity  # noqa: E402
from repro.models import lm  # noqa: E402

CONFIGS = ["qwen3-next-gdn", "mamba2-1.3b"]


def _both(name):
    conf = spec.load_config(name)
    h = conf["harness"]
    cfg = configs.get_arch(h["arch"])
    return conf, h, cfg


@pytest.mark.parametrize("name", CONFIGS)
def test_layout_is_the_programs_model(name):
    conf, h, cfg = _both(name)
    lay = h["layout"]
    assert h["full"] is True
    assert (lay["d_model"], lay["n_layers"], tuple(lay["pattern"]),
            lay["vocab"], lay["ffn"], lay["d_ff"], lay["norm_eps"],
            lay["tie_embeddings"], lay["act_dtype"], lay["state_dtype"]) == (
        cfg.d_model, cfg.n_layers, cfg.pattern, cfg.vocab, cfg.ffn,
        cfg.d_ff, cfg.norm_eps, cfg.tie_embeddings, cfg.act_dtype,
        cfg.state_dtype)
    m = lay["mixers"]
    if "gdn" in m:
        assert (m["gdn"]["k_heads"], m["gdn"]["v_heads"],
                m["gdn"]["head_dim"]) == (cfg.gdn_k_heads, cfg.gdn_v_heads,
                                          cfg.gdn_head_dim)
    if "attn" in m:
        assert (m["attn"]["heads"], m["attn"]["kv_heads"],
                m["attn"]["head_dim"], m["attn"]["rope_theta"]) == (
            cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rope_theta)
        assert not cfg.n_heads_pad and not cfg.n_kv_heads_pad
    if "ssm" in m:
        from repro.models.ssm import CONV_WIDTH
        assert (m["ssm"]["d_inner"], m["ssm"]["headdim"],
                m["ssm"]["d_state"], m["ssm"]["conv_width"]) == (
            cfg.ssm_d_inner, cfg.ssm_headdim, cfg.ssm_d_state, CONV_WIDTH)


def test_config_files_state_the_published_widths():
    q = spec.load_config("qwen3-next-gdn")
    lay = q["harness"]["layout"]
    assert (q["hidden_size"], q["num_hidden_layers"], q["vocab_size"],
            q["intermediate_size"]) == (lay["d_model"], lay["n_layers"],
                                        lay["vocab"], lay["d_ff"])
    assert (q["linear_num_key_heads"], q["linear_num_value_heads"],
            q["linear_key_head_dim"], q["linear_value_head_dim"]) == (
        16, 32, 128, 128)
    assert (q["num_attention_heads"], q["num_key_value_heads"],
            q["head_dim"]) == (16, 2, 128)
    m = spec.load_config("mamba2-1.3b")
    s = m["harness"]["layout"]
    lay = m["mamba2_layer"]
    assert (m["d_model"], m["n_layer"]) == (s["d_model"], s["n_layers"])
    assert lay["expand"] * m["d_model"] == s["mixers"]["ssm"]["d_inner"]
    assert (lay["headdim"], lay["d_state"], lay["d_conv"]) == (
        s["mixers"]["ssm"]["headdim"], s["mixers"]["ssm"]["d_state"],
        s["mixers"]["ssm"]["conv_width"])
    # the program pads the vocabulary to a multiple of 8, not of 16
    assert m["vocab_size"] <= s["vocab"] < m["vocab_size"] + 8
    assert s["vocab"] % 8 == 0 and not s["tie_embeddings"]


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_the_programs_weights(name):
    _, h, cfg = _both(name)
    shapes = jax.eval_shape(lambda k: lm.init_lm(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    assert Cost(h["layout"]).params == n


@pytest.mark.parametrize("name", CONFIGS)
def test_state_and_kv_bytes_match_cache_specs(name):
    _, h, cfg = _both(name)
    c = Cost(h["layout"])
    spec1 = lm.cache_specs(cfg, 1, h["max_len"])
    assert c.state_bytes == spec1.state_bytes
    assert c.kv_bytes_per_position * h["max_len"] == spec1.window_bytes
    assert intensity.arch_state_bytes(cfg) == c.state_bytes


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("ctx", [1, 300, 4096])
def test_mixer_flops_match_the_programs_decode_model(name, ctx):
    _, h, cfg = _both(name)
    c = Cost(h["layout"])
    prof = intensity.arch_decode_profile(cfg, seq=ctx)
    mixer = c.token_flops(ctx) - 2.0 * c.matmul_params
    assert mixer == pytest.approx(prof.flops)


def test_prompt_flops_sum_the_token_flops():
    c = Cost(spec.load_config("qwen3-next-gdn")["harness"]["layout"])
    n = 37
    assert c.prompt_flops(n) == pytest.approx(
        sum(c.token_flops(t) for t in range(1, n + 1)))


def test_decode_step_bytes_and_flops():
    c = Cost(spec.load_config("mamba2-1.3b")["harness"]["layout"])
    one = c.decode_step_bytes(1, 100)
    two = c.decode_step_bytes(2, 200)
    assert two - one == pytest.approx(2 * c.state_bytes + c.d * c.act)
    assert c.decode_step_flops(3, 300) == pytest.approx(
        3 * c.token_flops(100))
    # mamba2-1.3b at 32 live slots: the state stream is most of the bytes
    full = c.decode_step_bytes(32, 32 * 1000)
    assert 0.6 < 2 * 32 * c.state_bytes / full < 0.8
    assert np.isclose(c.weight_bytes / 2 ** 30, 2.5, atol=0.1)
