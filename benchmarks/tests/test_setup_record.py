"""The twelve readers of `benchmarks/lib/setup_record.py` on two records as chip runs left them:
`data/run_record_v5e_scopes.json` (the run's JSON of a traced `kimi-linear-ep16-1chip.seq16k` run
on a TPU v5e, PR 52, cut to what the readers use: its step's `jax::trace` span carries `scopes`,
`kernels` and `unscoped_s`) and `data/run_record_v5e.json` (`internlm2-1chip.seq4k`, PR 35: a
record from before the table, as a parent commit's is)."""

import copy
import importlib
import json
import os

import pytest

from benchmarks.lib import setup_record

HERE = os.path.dirname(os.path.abspath(__file__))
PROGRAMS = ("setup_step_program_s", "setup_apply_program_s", "setup_init_program_s", "setup_other_programs_s",
            "setup_outside_jax_s")
PARTS = ("step_trace_kernels_s", "step_trace_ends_s", "step_trace_autodiff_s", "step_trace_stack_s",
         "step_trace_unscoped_s")
TRACE = ("step_trace_s",) + PARTS + ("step_trace_scope_entries",)


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)["run"]


@pytest.fixture()
def run():
    return _load("run_record_v5e_scopes.json")


@pytest.fixture()
def old_run():
    return _load("run_record_v5e.json")


def _read(name, run):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read(run)


def test_the_helper_names_the_programs_as_the_program_does():
    from ray_tpu.models.lm import PROGRAMS as the_programs

    assert setup_record.PROGRAMS == the_programs
    assert set(setup_record.PROGRAM_ORDER) == set(the_programs.values()) | {setup_record.OTHER}
    assert [setup_record.program_of(n) for n in ("jit(_train_step)", "_train_step", "_forward", "jit(_init)",
                                                 "jit(convert_element_type)", "")] == [
        "step", "step", "apply", "init", "other", "other"]


@pytest.mark.parametrize("fixture", ["run", "old_run"])
def test_the_five_program_metrics_add_up_to_the_stretch(request, fixture):
    run = request.getfixturevalue(fixture)
    values = [_read(n, run) for n in PROGRAMS]
    assert all(isinstance(v, float) and v >= 0 for v in values), values
    assert sum(values) == pytest.approx(run["setup"]["t_window"] - run["start"]["t_loop"], abs=0.05)
    assert values[0] > values[1] > 0 and values[2] > 0 and values[3] > 0  # step > apply; each program cost something


def test_on_the_old_record_the_apply_program_shows_and_the_trace_metrics_read_nothing(old_run, capsys):
    assert _read("setup_apply_program_s", old_run) == pytest.approx(1.57, abs=0.01)  # 1.39 s of it the trace of `_forward`
    spans = [s for s in old_run["run_record"]["spans"] if s["attrs"].get("fun_name") == "_forward"]
    assert [round(s["end"] - s["start"], 2) for s in spans if s["name"] == "jax::trace"] == [1.39]
    assert [_read(n, old_run) for n in TRACE] == [None] * 7
    assert "trace-time" not in capsys.readouterr().out


def test_the_five_parts_add_up_to_the_steps_trace_and_the_guard_is_small(run, capsys):
    whole = _read("step_trace_s", run)
    parts = [_read(n, run) for n in PARTS]
    assert all(isinstance(v, float) and v >= 0 for v in parts), parts
    assert sum(parts) == pytest.approx(whole, abs=0.01)
    assert parts[-1] < 0.15 * whole
    (span,) = [s for s in run["run_record"]["spans"]
               if s["name"] == "jax::trace" and s["attrs"]["fun_name"] == "_train_step"]
    assert whole == pytest.approx(span["end"] - span["start"])
    assert _read("step_trace_scope_entries", run) == sum(row[1] for row in span["attrs"]["scopes"].values()) > 100
    said = [line for line in capsys.readouterr().out.splitlines() if line.startswith("[bench] trace-time ")]
    assert len(said) == 1  # one line a run, whichever reader is first
    top = json.loads(said[0][len("[bench] trace-time "):])["top"]
    assert len(top) == 10 and top == sorted(top, key=lambda row: -row[1])


def test_each_path_is_counted_to_one_part_the_first_it_meets():
    kernels = {"kda_fwd", "flash_fwd"}
    want = {"autodiff": "autodiff", "autodiff/layers": "stack", "autodiff/layers/layer/attn_core/kda/scan/kda_fwd": "kernels",
            "autodiff/kda_bwd": "stack", "autodiff/lm_head": "ends", "autodiff/loss/flash_fwd": "kernels",
            "optimizer": "ends", "autodiff/layers/layer/mlp/moe/experts": "stack", "autodiff/embed": "ends",
            "autodiff/layers/final_norm_like": "stack"}
    assert {path: setup_record.part_of(path, kernels) for path in want} == want


def test_a_span_outside_set_up_and_a_nested_program_are_not_counted_twice(run):
    late = copy.deepcopy(run)
    (span,) = [s for s in late["run_record"]["spans"]
               if s["name"] == "jax::trace" and s["attrs"]["fun_name"] == "_train_step"]
    after = dict(span, span_id="late", start=late["setup"]["t_window"] + 1.0, end=late["setup"]["t_window"] + 9.0)
    inside = dict(span, span_id="inside", name="jax::compile", attrs={"fun_name": "jit(iota)", "cache": "hit"},
                  start=span["start"] + 0.5, end=span["start"] + 1.0)  # an eager op compiled while the step is traced
    late["run_record"]["spans"] += [after, inside]
    assert [_read(n, late) for n in PROGRAMS + TRACE] == [_read(n, run) for n in PROGRAMS + TRACE]


@pytest.mark.parametrize("name", PROGRAMS + TRACE)
def test_a_reader_never_raises_and_reads_nothing_without_a_record(run, name, capsys):
    assert _read(name, dict(run, run_record=None)) is None
    empty = {"trace_id": "0", "spans": [], "runtime_spans": [], "stalls": [],
             "reports": {"count": 0, "polls": 0, "median_s": None, "max_s": None}}
    assert _read(name, dict(run, run_record=empty)) is None
    assert capsys.readouterr().out == ""
    assert _read(name, dict(run, run_record={"spans": "not a list"})) is None
    assert "[bench] run record FAILED" in capsys.readouterr().out
