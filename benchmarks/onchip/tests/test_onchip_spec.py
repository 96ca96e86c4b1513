"""The benchmark finds its pieces by name, refuses unknown ones, and
takes new ones without an edit; BENCHMARK.json keeps its format."""
import json
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import spec, traffic  # noqa: E402

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.mark.parametrize("loader,name", [
    (spec.load_config, "qwen3-next-gdn"), (spec.load_config, "mamba2-1.3b"),
    (spec.load_traffic, "batch_closed"),
    (spec.load_metric, "output_tokens_per_s"), (spec.load_metric, "mfu.tps"),
    (spec.load_mixer, "gdn"), (spec.load_mixer, "attn"),
    (spec.load_mixer, "ssm")])
def test_loaders_find_by_name(loader, name):
    assert loader(name)


@pytest.mark.parametrize("loader", [spec.load_config, spec.load_traffic,
                                    spec.load_metric, spec.load_mixer])
def test_loaders_refuse_unknown_names(loader):
    with pytest.raises(KeyError, match="nothing named 'nope'"):
        loader("nope")


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload named"):
        spec.find_workload(BENCH, "nope")


def test_new_pieces_need_only_new_files(tmp_path):
    """A config, a mix and a metric that exist only here load by name."""
    for d in ("configs", "traffic", "metrics"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "toy.json").write_text(json.dumps(
        {"source": "test", "harness": {"arch": "mamba2-1.3b", "slots": 2}}))
    (tmp_path / "traffic" / "trickle.json").write_text(json.dumps(
        {"kind": "closed_loop", "clients_per_slot": 3, "warm_s": 0.0,
         "prompt": {"dist": "uniform", "min": 3, "max": 5},
         "output": {"dist": "uniform", "min": 1, "max": 2},
         "sampling": {"temperature": 0.0}}))
    (tmp_path / "metrics" / "twice_setup.x.py").write_text(
        "def read(run):\n    return 2 * run.setup_s\n")
    bench = {"workloads": [{"name": "toy-trickle", "config": "toy",
                            "traffic": "trickle", "chips": 1}],
             "end_to_end": [], "per_layer": [
                 {"name": "twice_setup.x", "unit": "s",
                  "workloads": ["toy-trickle"]}]}
    w = spec.find_workload(bench, "toy-trickle")
    assert spec.load_config(w["config"], tmp_path)["harness"]["slots"] == 2
    mix = spec.load_traffic(w["traffic"], tmp_path)
    assert len(traffic.make(mix, 5, vocab=10, slots=2).due(1e9)) == 6
    [m] = spec.cell_metrics(bench, "toy-trickle", trace=True)
    reader = spec.load_metric(m["name"], tmp_path)

    class R:
        setup_s = 1.5
    assert reader.read(R()) == 3.0
    (tmp_path / "metrics" / "broken.py").write_text("x = 1\n")
    with pytest.raises(TypeError):
        spec.load_metric("broken", tmp_path)


def test_cell_metrics_follow_the_workloads_lists():
    bench = {"end_to_end": [{"name": "setup_s"},
                            {"name": "ttft_p95_ms", "workloads": ["chat"]}],
             "per_layer": [{"name": "queue_ms.ttft", "workloads": ["chat"]}]}
    assert [m["name"] for m in spec.cell_metrics(bench, "chat", False)] \
        == ["setup_s", "ttft_p95_ms"]
    assert [m["name"] for m in spec.cell_metrics(bench, "batch", False)] \
        == ["setup_s"]
    assert spec.cell_metrics(bench, "batch", True) == []
    for cell in ("mamba2-batch", "qwen3next-batch"):
        e2e = {m["name"] for m in spec.cell_metrics(BENCH, cell, False)}
        assert e2e == {"setup_s", "output_tokens_per_s"}
        layer = {m["name"] for m in spec.cell_metrics(BENCH, cell, True)}
        assert layer == {"active_slots.tps", "decode_roofline.tps",
                         "mfu.tps", "idle.tps"}


def test_benchmark_json_keeps_its_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "benchmarks/onchip/run.py"]
    assert BENCH["paths"] == ["benchmarks/onchip"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    root = spec.ROOT
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/onchip/")
        assert (root / c["file"]).is_file()
        assert c["file"] == f"benchmarks/onchip/configs/{c['name']}.json"
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        spec.load_traffic(w["traffic"])
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    cells = {w["name"] for w in BENCH["workloads"]}
    names = set()
    e2e = {}
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert e2e["setup_s"] == cells
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
        spec.load_metric(m["name"])
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert set(m["workloads"]) <= e2e[m["moves"]]
    for cell in cells:
        reported = [n for n, ws in e2e.items() if cell in ws]
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.cell_metrics(BENCH, cell, True)
    assert len(json.dumps(BENCH)) < 64 * 1024


# what the benchmark takes from the program: the system under test
ALLOWED = {"repro", "repro.configs", "repro.launch.serve",
           "repro.launch.compile_cache", "repro.models.lm",
           "repro.serving.engine"}


def test_the_yardstick_imports_nothing_of_the_program():
    """FLOPs, bytes, peaks, the trace reduction and the reference are the
    benchmark's own; from ``src/`` it imports only the serving path."""
    pat = re.compile(r"^\s*(?:from\s+(repro[\w.]*)\s+import\s+(.*)"
                     r"|import\s+(repro[\w.]*))")
    files = [p for p in spec.HERE.rglob("*.py") if "tests" not in p.parts]
    assert len(files) > 10
    for path in files:
        for line in path.read_text().splitlines():
            m = pat.match(line)
            if not m:
                continue
            if m.group(3):
                assert m.group(3) in ALLOWED, f"{path.name}: {line}"
                continue
            mod = m.group(1)
            for n in m.group(2).split(","):
                n = n.split("#")[0].strip()
                assert mod in ALLOWED - {"repro"} or f"{mod}.{n}" in ALLOWED, \
                    f"{path.name}: {line.strip()}"
    for path in (spec.HERE / "harness" / "reference.py",
                 spec.HERE / "harness" / "correct.py",
                 *(spec.HERE / "mixers").glob("*.py")):
        assert "repro" not in path.read_text(), path.name
