"""The readers of set-up's account (PR 36: ``setup_import_s``,
``setup_place_s``, the four ``setup_build_*_s``,
``setup_build_cache_misses``, ``setup_other_programs_s``,
``lm_window_compiles``, ``lm_step_temp_gib``) on records: the one beside
this file that a rehearsal of the looped cell wrote on the CPU
(``rehearsal_setup.record.json``: its set-up and a short window, whose
steps the file gives as ``window_steps``; seconds of a CPU, read here as
numbers only), that record with a fault
planted, and the record of PR 25 next to it, which holds none of the new
rows: what a parent of PR 36 gives. Run by hand: ``python3 -m pytest
benchmark/tests -q`` (not part of tier-1)."""

import importlib
import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
from benchmark import scopes  # noqa: E402

NEW = ("setup_import_s", "setup_place_s", "setup_build_trace_s",
       "setup_build_lower_s", "setup_build_backend_s",
       "setup_build_first_call_s", "setup_build_cache_misses",
       "setup_other_programs_s", "lm_window_compiles", "lm_step_temp_gib")
RUN = types.SimpleNamespace(window={})  # the fixture gives its steps


def read(name, run=RUN):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).read(run)


def _recorded(monkeypatch, name):
    with open(os.path.join(BENCH, "tests", "recorded_scoped", name)) as f:
        record = json.load(f)
    monkeypatch.setattr(scopes, "record", lambda: record)
    return record


@pytest.fixture
def rehearsal(monkeypatch):
    record = _recorded(monkeypatch, "rehearsal_setup.record.json")
    monkeypatch.setitem(RUN.window, "steps", record["window_steps"])
    return record


@pytest.mark.parametrize("name", NEW)
def test_a_record_without_the_new_rows_reads_nothing(monkeypatch, name):
    """... but ``setup_place_s``: its spans are in every record since
    PR 25."""
    _recorded(monkeypatch, "tiny_scoped.record.json")
    run = types.SimpleNamespace(window={"steps": 3})
    if name == "setup_place_s":
        assert read(name, run) > 0
    else:
        assert read(name, run) is None


@pytest.mark.parametrize("name", NEW)
def test_no_record_reads_nothing(monkeypatch, name):
    monkeypatch.setattr(scopes, "record", lambda: None)
    assert read(name, types.SimpleNamespace(window={"steps": 3})) is None


def _rows_s(record, name):
    """Seconds of the file's rows called ``name`` (none overlaps)."""
    return sum(e - s for n, s, e, _ in record["spans"] if n == name) / 1e9


def test_the_parts_of_the_builds_add_up_to_the_build_spans(rehearsal):
    parts = [read(f"setup_build_{p}_s")
             for p in ("trace", "lower", "backend", "first_call")]
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(read("setup_spmd_build_s"), rel=1e-9)
    # two builds in the file: the broadcast's and the step's
    assert parts[0] == pytest.approx(
        _rows_s(rehearsal, "hvd/spmd/build/trace"))
    assert parts[1] == pytest.approx(
        _rows_s(rehearsal, "hvd/spmd/build/lower"))
    assert parts[2] == pytest.approx(
        _rows_s(rehearsal, "hvd/spmd/build/compile"))
    assert 0.071 + 1.619 < parts[0] < 0.072 + 1.622  # by eye, from the file


def test_import_placement_and_the_other_programs(rehearsal):
    assert read("setup_import_s") == pytest.approx(0.714150149)
    spans = rehearsal["spans"]
    by_hand = sum(e - s for n, s, e, _ in spans if n in (
        "hvd/replicate", "hvd/rank_stack", "hvd/broadcast")) - sum(
        e - s for n, s, e, p in spans
        if n == "hvd/spmd/build" and p == "hvd/broadcast")
    assert read("setup_place_s") == pytest.approx(by_hand / 1e9)
    assert 0 < read("setup_place_s") < 1
    sums = rehearsal["compiles"]
    assert read("setup_other_programs_s") == pytest.approx(sum(
        sums[w][k] for w in sums for k in ("trace_s", "lower_s",
                                           "backend_s")))
    assert read("setup_build_cache_misses") == 0
    assert read("lm_step_temp_gib") == pytest.approx(4765944 / 2 ** 30)


def test_a_clean_window_compiles_nothing(rehearsal):
    assert read("lm_window_compiles") == 0
    assert read("lm_window_builds") == 0


@pytest.mark.parametrize("where,programs,counted", [
    ("in_dispatch", 2, 2),     # jax.jit compiled again under the same key
    ("after_dispatch", 40, 1)  # the runner's own: at least one was late
])
def test_a_compile_no_build_span_holds_is_counted(rehearsal, where,
                                                  programs, counted):
    first = scopes.window_dispatches(RUN, rehearsal)[0][1]
    rehearsal["compiles"][where].update(programs=programs,
                                        last_ns=first + 5_000_000)
    assert read("lm_window_compiles") == counted
    assert read("lm_window_builds") == 0


def test_a_build_in_the_window_is_counted_by_both(rehearsal):
    first = scopes.window_dispatches(RUN, rehearsal)[0][1]
    rehearsal["spans"] += [
        ["hvd/spmd/build/trace", first + 10, first + 20, "hvd/spmd/build"],
        ["hvd/spmd/build/load", first + 20, first + 30, "hvd/spmd/build"],
        ["hvd/spmd/build", first + 5, first + 40, None]]
    assert read("lm_window_compiles") == 1
    assert read("lm_window_builds") == 1
    # ... and is no part of set-up
    assert read("setup_build_backend_s") == pytest.approx(
        _rows_s(rehearsal, "hvd/spmd/build/compile"))


def test_cold_and_warm_runs_are_told_apart(rehearsal):
    for p in rehearsal["programs"].values():
        p["counters"]["build.cache_misses"] = 1
    assert read("setup_build_cache_misses") == 2
