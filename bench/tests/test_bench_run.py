"""A whole run off the chip: the rehearsal of the command line, and the
comparison seeing ``correct`` fail for the control and for faults
planted under the timed path."""

import dataclasses
import json
import os
import subprocess
import sys
import time
import types

import jax
import numpy as np
import pytest

from harness import checks, manifest, session

from repro import api

ROOT = manifest.ROOT


@pytest.fixture(scope="module")
def cell():
    return manifest.rehearsal(manifest.cell(manifest.load(), "paper.fig8c"))


def serve(cell, seed):
    return session.serve(cell, seed=seed, seconds=1.0, trace=False,
                         t_start=time.monotonic(), devices=jax.devices())


def numbers(rows):
    return {name: value for name, value, _ in rows}


def test_sound_run_is_correct_and_the_control_is_not(cell):
    s = serve(cell, 2**32 + 11)
    assert s.crash is None
    assert s.records.done.all() and s.records.done.sum() > 1000
    sound = checks.compare_served(s)
    assert checks.passed(sound), sound
    ctrl = checks.control(s, cell["config_data"]["admission"]["max_width"])
    assert not checks.passed(ctrl), ctrl
    assert numbers(ctrl)["result_mismatches"] > 0


def test_an_answer_altered_where_it_is_produced_is_caught(cell, monkeypatch):
    confirm = api.Uruv.confirm

    def altered(self, pending):
        res = confirm(self, pending)
        if res is None:
            return res
        values = np.array(res.values)
        values[0] += 1
        return dataclasses.replace(res, values=values)

    monkeypatch.setattr(api.Uruv, "confirm", altered)
    got = numbers(checks.compare_served(serve(cell, 12)))
    assert got["result_mismatches"] > 0


def test_a_pass_that_leaves_the_store_unchanged_is_caught(cell, monkeypatch):
    apply_nowait = api.LocalExecutor.apply_nowait

    def frozen(self, store, batch, **kw):
        _, values, ok = apply_nowait(self, store, batch, **kw)
        return store, values, ok

    monkeypatch.setattr(api.LocalExecutor, "apply_nowait", frozen)
    got = numbers(checks.compare_served(serve(cell, 13)))
    assert got["order_violations"] > 0
    assert got["readback_mismatches"] > 0


def cli(tmp_path, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper.fig8c",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_without_a_chip_the_command_prints_no_result(tmp_path):
    r = cli(tmp_path)
    assert r.returncode == 2
    assert r.stdout == ""
    assert "no result" in r.stderr


def test_the_rehearsal_runs_the_path_and_prints_no_metric(tmp_path):
    r = cli(tmp_path, "--rehearse")
    assert r.returncode == 1, r.stderr[-3000:]
    assert r.stdout == ""
    for name, limit in checks.LIMITS.items():
        assert f"check {name} = 0 (limit {limit})" in r.stderr
    assert "not a chip run, no metric" in r.stderr
    assert "latency_ms p50/p90/p99/p99.9/max = " in r.stderr
    with pytest.raises(json.JSONDecodeError):
        json.loads(r.stderr.strip().splitlines()[-1])


def _latencies():
    """100 requests submitted in the window [0, 10] with latencies of
    1..100 ms, one submitted before it (completed in it: 101 in the
    window) and one never completed."""
    submit = np.concatenate([[-1.0], np.linspace(0.5, 9.5, 100), [9.9]])
    lat = np.concatenate([[5.0], np.arange(1, 101) * 1e-3, [np.nan]])
    done_t = submit + lat
    rec = types.SimpleNamespace(submit_t=submit, done_t=done_t,
                                done=~np.isnan(done_t))
    return types.SimpleNamespace(records=rec, t0=0.0, t1=10.0, seconds=10.0)


@pytest.mark.parametrize("metric, want", [
    ("req_p50_ms", 50.5), ("req_p99_ms", 99.01), ("ops_per_s", 10.1)])
def test_latency_and_rate_readers_on_known_requests(metric, want):
    assert manifest.reader(metric)(_latencies()) == pytest.approx(want)


def test_stalls_name_the_gaps_between_completions():
    run = _latencies()
    run.records.done_t[50:] += 0.5          # one 0.5 s stall mid-window
    s = types.SimpleNamespace(**vars(run), gc_pauses=[(2, 0.004)],
                              slow_turns=[(5.0, 0.5, 0.45, 0.01, 0.02)],
                              trace=None)
    lines = session.stalls(s).splitlines()
    assert lines[0].startswith("latency_ms p50/p90/p99/p99.9/max = ")
    assert lines[1].startswith("completion gaps > 100 ms: 1, ")
    assert lines[2].startswith("gc pauses: 1 (gen2 1), 4.0 ms in all")
    assert lines[3].endswith("[(500.0, 450.0, 10.0, 20.0)]")
