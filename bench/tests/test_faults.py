"""A run with its chip check skipped, at a small size on the CPU: sound it
comes out correct, and with the timed path broken underneath it comes out
not correct, once for each fault a partition cell can have. The controls
of ``control.py`` come out not correct too."""
import dataclasses
import json

import jax
import numpy as np
import pytest

import control
import graphs
import harness
import kernels
import run

CELLS = {"snn_rand_truenorth.resolve": 256}


def _small(monkeypatch, cell):
    real = harness.config

    def small(name, bench=harness.BENCH):
        return dict(real(name, bench), n_nodes=CELLS[cell])
    monkeypatch.setattr(harness, "config", small)
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)


def _result(capsys, cell, trace=0):
    assert run.main(["--workload", cell, "--seed", "3000000019",
                     "--seconds", "0.1", "--trace", str(trace)]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _program_connectivity(hg, parts):
    from repro.core import metrics
    return metrics.connectivity(hg, parts)


def _state_unchanged(res, hg):
    ident = np.arange(hg.n_nodes)
    return dataclasses.replace(res, parts=ident,
                               connectivity=_program_connectivity(hg, ident))


def _half_left_out(res, hg):
    parts = res.parts.copy()
    parts[hg.n_nodes // 2:] = -1
    return dataclasses.replace(res, parts=parts)


def _answer_altered(res, hg):
    parts = res.parts.copy()
    parts[0] = parts.max() + 1
    return dataclasses.replace(res, parts=parts)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(monkeypatch, capsys, cell):
    _small(monkeypatch, cell)
    out = _result(capsys, cell)
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "solve_s", "connectivity",
                                   "parts"}
    assert out["failed"] == 0 and out["attempted"] >= 2


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault,number", [
    (_state_unchanged, "block_share"),
    (_half_left_out, "unassigned"),
    (_answer_altered, "conn_gap"),
])
def test_broken_solve_is_not_correct(monkeypatch, capsys, cell, fault,
                                     number):
    _small(monkeypatch, cell)
    real = run.solve
    monkeypatch.setattr(run, "solve",
                        lambda hg, cfg: fault(real(hg, cfg), hg))
    out = _result(capsys, cell)
    assert out["correct"] is False
    chk = out["checks"][number]
    assert chk["value"] > chk["limit"]
    assert out["failed"] == out["attempted"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_broken_kernel_is_not_correct(monkeypatch, capsys, cell):
    _small(monkeypatch, cell)
    real = kernels.program_outputs
    monkeypatch.setattr(kernels, "program_outputs",
                        lambda x: control.kernel_altered(real(x)))
    out = _result(capsys, cell)
    assert out["correct"] is False
    for k in ("gains_err", "pair_scores_err", "pins_count_err"):
        assert out["checks"][k]["value"] > 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_controls_and_faults_are_not_correct(monkeypatch, cell):
    _small(monkeypatch, cell)
    cfg = harness.config(harness.workload(cell)["config"])
    from repro.core.hypergraph import Caps
    mix = harness.traffic(harness.workload(cell)["traffic"])
    g, = graphs.requests(cfg, mix, 11)
    hg = harness.program_graph(g)
    rec = run.record(run.solve(hg, cfg), 0.0)
    x = kernels.inputs(Caps.for_host(hg), rec["kcap"], 11)
    out = control.readings(g, cfg, rec, x, kernels.program_outputs(x))
    ok = {name: all(v["value"] <= v["limit"] for v in nums.values())
          for name, nums in out.items()}
    assert ok == dict(sound=True, control=False, state_unchanged=False,
                      half_left_out=False, answer_altered=False)
    assert out["control"]["max_inbound"]["value"] > cfg["delta"] or \
        out["control"]["gains_err"]["value"] > 0

