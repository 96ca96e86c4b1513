"""The join of the serving program's tick and prefill logs to a device
trace, on a small synthetic trace: each reader's value, the scope split of
the decode program, the idle gaps labelled by program spans; and the
profiling tool's whole path on the CPU at a reduced size."""
import json
import sys
from pathlib import Path

import pytest
from jax.profiler import ProfileData

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import profile_cell  # noqa: E402
from harness import cell, program  # noqa: E402
from harness import trace as trace_mod  # noqa: E402


def _plane(pid, name, lines):
    """A text XPlane: ``lines`` maps a line name to events (event name,
    start ns, duration ns, stats dict)."""
    names, stats = {}, {}
    body = ""
    for lid, (line, events) in enumerate(lines.items(), 1):
        evs = ""
        for ev, start, dur, st in events:
            mid = names.setdefault(ev, len(names) + 1)
            sts = ""
            for k, v in st.items():
                sid = stats.setdefault(k, len(stats) + 1)
                val = (f'str_value: "{v}"' if isinstance(v, str)
                       else f"int64_value: {v}")
                sts += f"stats {{ metadata_id: {sid} {val} }} "
            evs += (f"events {{ metadata_id: {mid} offset_ps: {start * 1000}"
                    f" duration_ps: {dur * 1000} {sts}}}\n")
        body += (f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0\n'
                 f"{evs}}}\n")
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in names.items())
    smeta = "".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}\n' for n, i in stats.items())
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}{smeta}}}\n'


# times in ns; the traced span is [100, 2100).  Tick 1 prefills one
# prompt (a scan, then its admit) and decodes; tick 2 only decodes.  A
# decode run at [105, 150) was launched before the trace began.
HOST = [
    ("bench:window", 100, 2000, {}),
    ("bench:router_step", 150, 1000, {}),
    ("serve:step", 155, 985, {"tick": 1}),
    ("serve:admit", 160, 140, {"tick": 1}),
    ("serve:prefill.dispatch", 170, 20, {"tick": 1,
                                         "program": "prefill_scan"}),
    ("serve:prefill.dispatch", 200, 20, {"tick": 1, "program": "admit"}),
    ("serve:prefill.sync", 230, 60, {"tick": 1}),
    ("serve:decode.dispatch", 310, 20, {"tick": 1}),
    ("serve:decode.sync", 340, 560, {"tick": 1}),
    ("serve:emit", 905, 195, {"tick": 1}),
    ("bench:router_step", 1200, 850, {}),
    ("serve:step", 1205, 840, {"tick": 2}),
    ("serve:admit", 1210, 40, {"tick": 2}),
    ("serve:scatter", 1212, 10, {"tick": 2}),
    ("serve:decode.dispatch", 1260, 20, {"tick": 2}),
    ("serve:decode.sync", 1290, 610, {"tick": 2}),
    ("serve:emit", 1905, 95, {"tick": 2}),
]
MODULES = [("jit_serve_decode(11)", 105, 45, {}),
           ("jit_serve_prefill_scan(12)", 195, 30, {}),
           ("jit_serve_admit(13)", 226, 64, {}),
           ("jit_serve_decode(11)", 335, 565, {}),
           ("jit_serve_scatter(14)", 1215, 10, {}),
           ("jit_serve_decode(11)", 1285, 615, {})]
OPS = [("fusion.1", 105, 45, {}), ("fusion.9", 195, 30, {}),
       ("fusion.8", 226, 64, {}),
       ("fusion.1", 340, 260, {}), ("fusion.2", 600, 200, {}),
       ("copy.3", 800, 100, {}), ("copy.7", 1215, 10, {}),
       ("fusion.1", 1290, 210, {}), ("fusion.2", 1500, 300, {}),
       ("copy.3", 1800, 100, {})]
TEXT = (_plane(1, "/device:TPU:0", {"XLA Modules": MODULES, "XLA Ops": OPS})
        + _plane(3, "/host:CPU", {"python": HOST}))
TICK_LOG = [{"tick": 1, "k": 2, "live": [2, 2], "ctx": [100, 102]},
            {"tick": 2, "k": 2, "live": [2, 1], "ctx": [104, 53]}]
PREFILL_LOG = [{"tick": 1, "program": "prefill_scan", "rows": [(7, 0, 32)]},
               {"tick": 1, "program": "admit", "rows": [(7, 32, 5)]}]
HLO = """
  %fusion.1 = f32[2]{0} fusion(%p), kind=kLoop, calls=%c.1, metadata={op_type="mul" op_name="jit(serve_decode)/while/body/mixer_gdn/mul" source_file="x.py"}
  %fusion.2 = f32[2]{0} fusion(%fusion.1), kind=kLoop, calls=%c.2, metadata={op_name="jit(serve_decode)/while/body/ffn/dot_general"}
  %copy.3 = f32[2]{0} copy(%fusion.2), metadata={op_name="jit(serve_decode)/while/body/closed_call"}
  %tuple.4 = (f32[2]{0}) tuple(%copy.3)
"""


class Cost:
    """Round numbers for the readers: a step moves 1000 bytes per live
    slot plus one per position, and computes 10 FLOPs per live slot; a
    prompt token costs 2 FLOPs."""
    weight_bytes = 500
    state_bytes = 200
    kv_bytes_per_position = 1

    def decode_step_bytes(self, live, ctx):
        return 1000 * live + ctx

    def decode_step_flops(self, live, ctx):
        return 10 * live

    def prompt_flops(self, n):
        return 2 * n


PEAK = {"hbm_bytes_per_s": 1e10, "bf16_flops": 1e9}


@pytest.fixture(scope="module")
def prof():
    return program.read_profile(ProfileData.from_text_proto(TEXT))


def test_profile_keeps_program_runs_and_spans(prof):
    assert [f for f, _, _ in prof["modules"]["/device:TPU:0"]] == [
        "decode", "prefill_scan", "admit", "decode", "scatter", "decode"]
    assert len(prof["ops"]["/device:TPU:0"]) == len(OPS)
    assert len(prof["spans"]) == len(HOST)
    assert program.window(prof["spans"]) == (100, 2100)
    assert program.family("jit__lambda_(3)") is None


def test_runs_join_the_ticks_that_dispatched_them(prof):
    joined = program.join(prof["modules"]["/device:TPU:0"], prof["spans"])
    # the run launched before the trace began pairs with no span
    assert joined == [(1, "decode", 335, 900), (2, "decode", 1285, 1900),
                      (1, "prefill_scan", 195, 225), (1, "admit", 226, 290)]


def test_a_run_missing_from_the_trace_shifts_no_pairing(prof):
    """The device's record may begin after the host's: a dispatch span
    whose run is missing pairs with nothing, and the runs after it still
    pair with their own spans."""
    runs = [r for r in prof["modules"]["/device:TPU:0"]
            if r[1:] != (335, 900)]
    assert [j for j in program.join(runs, prof["spans"])
            if j[1] == "decode"] == [(2, "decode", 1285, 1900)]


def test_a_device_clock_ahead_of_the_host_shifts_no_pairing(prof):
    """Runs that read 100 ns late against the host's clock (past their
    syncs' ends) still join their own ticks."""
    late = [(f, s + 100, e + 100) for f, s, e in
            prof["modules"]["/device:TPU:0"]]
    assert program.join(late, prof["spans"]) == [
        (1, "decode", 435, 1000), (2, "decode", 1385, 2000),
        (1, "prefill_scan", 295, 325), (1, "admit", 326, 390)]


def test_decode_device_roofline(prof):
    joined = program.join(prof["modules"]["/device:TPU:0"], prof["spans"])
    # tick 1: 2100 + 2102 bytes at 1e10 B/s; tick 2: 2104 + 1053 bytes;
    # over 565 + 615 ns of decode runs
    want = 100 * (4202 + 3157) / 1e10 / 1180e-9
    got = program.decode_device_roofline(joined, TICK_LOG, 100, 2100,
                                         Cost(), PEAK)
    assert got == pytest.approx(want)
    # a run that ends past the traced span does not count
    assert program.decode_device_roofline(
        joined, TICK_LOG, 100, 1800, Cost(), PEAK) == pytest.approx(
            100 * 4202 / 1e10 / 565e-9)
    assert program.decode_device_roofline([], TICK_LOG, 100, 2100, Cost(),
                                          PEAK) is None


def test_prefill_device_roofline(prof):
    joined = program.join(prof["modules"]["/device:TPU:0"], prof["spans"])
    # bytes 500 + 200 + 37 = 737 at 1e10 (73.7 ns); FLOPs 2 * 37 = 74 at
    # 1e9 (74 ns): the larger, over 30 + 64 ns of scan and admit
    got = program.prefill_device_roofline(joined, PREFILL_LOG, 100, 2100,
                                          Cost(), PEAK)
    assert got == pytest.approx(100 * 74e-9 / 94e-9)
    # a tick whose prefill ran only in part inside the span does not count
    assert program.prefill_device_roofline(
        joined, PREFILL_LOG, 200, 2100, Cost(), PEAK) is None


def test_scope_split_of_the_decode_program(prof):
    scopes = program.op_scopes(HLO)
    # an instruction with no op_name is left out, and reads as outside
    assert scopes == {"fusion.1": "mixer_gdn", "fusion.2": "ffn",
                      "copy.3": program.OUTSIDE}
    joined = program.join(prof["modules"]["/device:TPU:0"], prof["spans"])
    split = program.scope_split(
        prof["ops"]["/device:TPU:0"],
        [(f, s, e) for _, f, s, e in joined], "decode", scopes, 100, 2100)
    assert split == {"mixer_gdn": pytest.approx(470e-9),
                     "ffn": pytest.approx(500e-9),
                     program.OUTSIDE: pytest.approx(200e-9)}


def test_idle_gaps_carry_program_labels(prof, monkeypatch):
    monkeypatch.setattr(trace_mod, "SHORT_S", 0.0)
    gaps = program.idle_gaps(prof["ops"], prof["spans"], 100, 2100)
    labels = {(round(mid - sec * 5e8), round(mid + sec * 5e8)): name
              for name, sec, mid in gaps}
    assert labels == {(100, 105): "outside harness spans",
                      (150, 195): "serve:prefill.dispatch",
                      (225, 226): "serve:admit",
                      (290, 340): "serve:decode.dispatch",
                      (900, 1215): "serve:emit",
                      (1225, 1290): "serve:step",
                      (1900, 2100): "serve:step"}
    # the harness's own reduction labels the same gaps by its spans alone
    red = trace_mod.reduce_profile(ProfileData.from_text_proto(TEXT))
    assert sorted(round(sec * 1e9) for _, sec in red["idle_gaps"]) == \
        sorted(round(sec * 1e9) for _, sec, _ in gaps)
    assert {n for n, _ in red["idle_gaps"]} == {"outside harness spans",
                                               "router_step"}


def test_self_time_reader():
    assert program.sched_self_ms({"steps": 4, "sched_self_s": 0.01}) \
        == pytest.approx(2.5)
    assert program.sched_self_ms({"steps": 0, "sched_self_s": 0.0}) is None


def test_gap_anatomy(prof):
    joined = program.join(prof["modules"]["/device:TPU:0"], prof["spans"])
    runs = prof["modules"]["/device:TPU:0"]
    # between the two decode runs the scatter ran: no pair is clean
    assert profile_cell.anatomy(runs, joined, prof["spans"])["pairs"] == 0
    clean = [r for r in runs if r[0] != "scatter"]
    a = profile_cell.anatomy(clean, joined, prof["spans"])
    # run 1 ends 900, its sync ends 900, tick 2 dispatches at 1260 and
    # its run starts at 1285
    assert a == {"result_ms": 0.0, "host_ms": pytest.approx(360e-6),
                 "launch_ms": pytest.approx(25e-6),
                 "idle_ms": pytest.approx(385e-6), "pairs": 1}
    assert profile_cell.overlapping(prof["spans"], 1000, 1210)[-1] == [
        "serve:step", 2, pytest.approx(5e-6)]


def test_the_profiling_tool_runs_on_the_cpu(tmp_path):
    """The tool's whole path at a reduced size: no device plane and no
    peaks here, so the device readers read None, and the program's own
    counters are read."""
    for d in ("configs", "traffic"):
        (tmp_path / d).mkdir()
    (tmp_path / "metrics").symlink_to(
        Path(__file__).resolve().parents[1] / "metrics")
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps({
        "source": "test", "harness": {
            "arch": "qwen3-next-gdn", "full": False, "slots": 2,
            "max_len": 128, "limits": {"served_len_mismatch": 0},
            "layout": {"d_model": 64, "n_layers": 4, "vocab": 256,
                       "pattern": ["gdn", "gdn", "gdn", "attn"],
                       "ffn": "dense", "d_ff": 128, "norm_eps": 1e-6,
                       "tie_embeddings": False, "act_dtype": "float32",
                       "state_dtype": "float32",
                       "mixers": {"gdn": {"k_heads": 2, "v_heads": 4,
                                          "head_dim": 16},
                                  "attn": {"heads": 4, "kv_heads": 2,
                                           "head_dim": 16,
                                           "rope_theta": 10000.0}}}}}))
    (tmp_path / "traffic" / "tiny.json").write_text(json.dumps({
        "kind": "closed_loop", "clients_per_slot": 2, "warm_s": 0.5,
        "prompt": {"dist": "loguniform", "min": 4, "max": 40},
        "output": {"dist": "loguniform", "min": 8, "max": 16},
        "sampling": {"temperature": 0.0, "top_k": 0, "top_p": 1.0,
                     "greedy_share": 1.0}}))
    bench = {"workloads": [{"name": "tiny", "config": "tiny",
                            "traffic": "tiny", "chips": 1}]}
    eng = cell.Engine(bench, "tiny", 2 ** 31 + 5, base=tmp_path,
                      require_tpu=False, use_cache=False)
    r = profile_cell.measure(eng, 2 ** 31 + 5, 1.5, trace_s=0.5)
    assert r["device"]["platform"] == "cpu"
    assert r["compiles"] == 0 and r["steps"] > 0 and r["ticks"] > 0
    assert r["sched_self_ms"] > 0 and r["sched_self_ms.traced"] > 0
    for k in ("decode_roofline.tps", "decode_device_roofline",
              "prefill_device_roofline"):
        assert r[k] is None
    assert r["joined"] == {} and r["idle_gaps"] == []
    assert r["gap_anatomy"]["pairs"] == 0
    assert r["host_phases"]["serve:step"]["n"] > 0
    assert any("serve:" in str(v) for v in r["samples"].values())
