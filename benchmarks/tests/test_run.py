"""The harness: it refuses to report without a TPU, BENCHMARK.json and the
files it names agree, every cell rehearses on the CPU, and a fifth cell and
a new per-layer metric need files and entries only: no edit of a file that
is there."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def _run(args, cwd=ROOT, env=None, timeout=600):
    env = dict(os.environ if env is None else env)
    proc = subprocess.run([sys.executable, "benchmarks/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    return proc, lines


def _is_result(lines):
    try:
        return bool(lines) and "metrics" in json.loads(lines[-1])
    except ValueError:
        return False


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env.update(extra)
    return env


def test_refuses_to_report_when_jax_is_held_to_the_cpu():
    proc, lines = _run(["--workload", CELLS[0], "--seed", "0", "--seconds", "1", "--trace", "0"],
                       env=_env(JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0 and not _is_result(lines)
    assert "rules out the TPU" in proc.stdout


def test_refuses_to_report_when_the_host_has_no_chip():
    if os.path.isdir("/dev/vfio") and any(n.isdigit() for n in os.listdir("/dev/vfio")):
        pytest.skip("this host has a chip")
    proc, lines = _run(["--workload", CELLS[0], "--seed", "0", "--seconds", "1", "--trace", "0"],
                       env=_env())
    assert proc.returncode != 0 and not _is_result(lines)
    assert "TPU chip(s)" in proc.stdout


def test_refuses_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc, lines = _run(["--workload", CELLS[0], "--seed", "0", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=_env(PYTHONPATH=""))
    assert proc.returncode != 0 and not _is_result(lines)


def test_benchmark_json_and_the_files_it_names_agree():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs", "workloads",
                              "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["benchmarks"]
    four_chip = [w for w in BENCHMARK["workloads"] if w["chips"] == 4]
    assert len(four_chip) <= max(1, len(CELLS) // 4)
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    for w in BENCHMARK["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4) and w["config"] in configs
        assert os.path.isfile(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    for c in BENCHMARK["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"]) == ["num_hidden_layers"]
        assert cfg["source"] == c["source"]
        assert cfg["reduced"]["num_hidden_layers"]["to"] == cfg["num_hidden_layers"]
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCHMARK["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    from benchmarks import run as harness

    readers = harness.layer_metric_readers()
    assert set(readers) == {m["name"] for m in BENCHMARK["per_layer"]}
    for m in BENCHMARK["per_layer"]:
        r = readers[m["name"]]
        assert (r.layer, r.unit, r.moves, r.source) == (m["layer"], m["unit"], m["moves"], m["source"])
        assert getattr(r, "cells", None) == m.get("workloads")
        assert m["moves"] in e2e and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])


def test_widths_equal_the_source_and_only_depth_is_cut():
    def load(name):
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            return json.load(f)

    one, four, intern = (load("mistral-7b-v0.3-1chip"), load("mistral-7b-v0.3-fsdp4"),
                         load("internlm2-1.8b-1chip"))
    published = {"hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "vocab_size": 32768, "rms_norm_eps": 1e-5,
                 "rope_theta": 1e6, "max_position_embeddings": 32768, "sliding_window": None,
                 "tie_word_embeddings": False}
    for cfg in (one, four):
        assert {k: cfg[k] for k in published} == published
        assert cfg["reduced"]["num_hidden_layers"]["from"] == 32
    shape = lambda c: {k: v for k, v in c.items()  # noqa: E731
                       if k not in ("num_hidden_layers", "reduced", "train", "deployment", "distortion")}
    assert shape(one) == shape(four)
    assert one["train"]["remat_policy"] == four["train"]["remat_policy"] == intern["train"]["remat_policy"]
    published = {"hidden_size": 2048, "intermediate_size": 8192, "num_attention_heads": 16,
                 "num_key_value_heads": 8, "vocab_size": 92544, "rms_norm_eps": 1e-5,
                 "rope_theta": 1000000, "max_position_embeddings": 32768, "tie_word_embeddings": False}
    assert {k: intern[k] for k in published} == published
    assert intern["reduced"]["num_hidden_layers"]["from"] == 24


def _rehearse(cell, cwd=ROOT, trace="0", env=None):
    proc, lines = _run(["--workload", cell, "--seed", "3", "--seconds", "3", "--trace", trace,
                        "--rehearse"], cwd=cwd, env=env or _env())
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = json.loads(lines[-1])
    assert out["rehearsal"] is True and out["correct"] is True and out["failed"] == 0
    assert "metrics" not in out  # a rehearsal is never a result
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_rehearses_on_the_cpu(cell):
    out = _rehearse(cell)
    chips = next(w["chips"] for w in BENCHMARK["workloads"] if w["name"] == cell)
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": chips}
    assert "tokens_per_s_per_chip" in out["metric_names"] and "setup_s" in out["metric_names"]


def _digest(top):
    out = {}
    for d, _, files in os.walk(top):
        for n in files:
            p = os.path.join(d, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, top)] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_a_fifth_cell_and_a_new_layer_metric_need_files_and_entries_only(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = _digest(tmp_path / "benchmarks")
    # new files: a mix, a configuration, a reader
    with open(os.path.join(BENCH, "traffic", "seq1k.json")) as f:
        mix = json.load(f)
    mix.update(seq_len=128, seqs_per_chip=3)
    (tmp_path / "benchmarks/traffic/seq128x3.json").write_text(json.dumps(mix))
    with open(os.path.join(BENCH, "configs", "internlm2-1.8b-1chip.json")) as f:
        cfg = json.load(f)
    (tmp_path / "benchmarks/configs/internlm2-copy.json").write_text(json.dumps(cfg))
    (tmp_path / "benchmarks/layer_metrics/steps_in_window.py").write_text(
        'layer = "train step host side"\nunit = "steps"\nsource = "program_counter"\n'
        'moves = "tokens_per_s_per_chip"\ncells = ["internlm2-copy.seq128x3"]\n\n\n'
        'def read(run):\n    return run["summary"]["steps_completed"]\n')
    # new entries
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "internlm2-copy", "source": cfg["source"],
                             "file": "benchmarks/configs/internlm2-copy.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "internlm2-copy.seq128x3", "config": "internlm2-copy",
                               "traffic": "seq128x3", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_in_window", "unit": "steps", "better": "higher",
                               "source": "program_counter", "layer": "train step host side",
                               "moves": "tokens_per_s_per_chip",
                               "workloads": ["internlm2-copy.seq128x3"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = _rehearse("internlm2-copy.seq128x3", cwd=tmp_path, trace="1", env=_env(PYTHONPATH=ROOT))
    assert "steps_in_window" in out["metric_names"]
    assert "collective_time_pct" not in out["metric_names"]  # not this cell's
    after = _digest(tmp_path / "benchmarks")
    after = {k: v for k, v in after.items() if not k.startswith("out/") and "__pycache__" not in k}
    assert {k: after[k] for k in before} == before  # nothing that was there changed
    assert set(after) - set(before) == {"traffic/seq128x3.json", "configs/internlm2-copy.json",
                                        "layer_metrics/steps_in_window.py"}
