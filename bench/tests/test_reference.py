"""The plain audit and kernel references against the program's own audit
and oracles, and the controls against the checks."""
import ml_dtypes
import numpy as np
import pytest

import graphs
import harness
import kernels
import reference


def _program_audit(g, parts, omega, delta):
    from repro.core import metrics
    return metrics.audit(harness.program_graph(g), parts, omega, delta)


@pytest.mark.parametrize("make,seed", [
    (lambda s: graphs.snn_smallworld(128, 16, 0.35, s), 1),
    (lambda s: graphs.snn_smallworld(200, 8, 0.5, s), 2),
    (lambda s: graphs.snn_layered(96, 32, 6, 12, s), 3),
    (lambda s: graphs.shuffle_edges(graphs.snn_layered(128, 32, 6, 12, s), 9),
     4),
])
@pytest.mark.parametrize("k", [1, 3, 17])
def test_audit_agrees_with_program_audit(make, seed, k):
    g = make(seed)
    parts = np.random.default_rng(seed).integers(0, k, g["n_nodes"])
    parts = np.unique(parts, return_inverse=True)[1]  # compact ids
    mine = reference.audit(g, parts, 40, 60)
    theirs = _program_audit(g, parts, 40, 60)
    assert mine["unassigned"] == 0
    assert mine["n_blocks"] == theirs["n_parts"]
    assert mine["max_block"] == theirs["max_size"]
    assert mine["max_inbound"] == theirs["max_inbound"]
    assert mine["connectivity"] == theirs["connectivity"]


def test_audit_flags_unassigned_and_bad_shapes():
    g = graphs.snn_smallworld(64, 8, 0.35, 0)
    parts = np.zeros(64, np.int64)
    parts[5] = -1
    assert reference.audit(g, parts, 64, 64)["unassigned"] == 1
    assert reference.audit(g, parts[:10], 64, 64)["unassigned"] == 64
    ident = np.arange(64)
    assert reference.audit(g, ident, 64, 64)["cut_share"] == 1.0


def test_shuffle_keeps_the_graph_and_is_seeded():
    g = graphs.snn_smallworld(256, 16, 0.35, 0)
    a, b = graphs.shuffle_edges(g, 5), graphs.shuffle_edges(g, 5)
    assert all(np.array_equal(a[k], b[k]) for k in a if k != "n_nodes")
    c = graphs.shuffle_edges(g, 2 ** 40 + 7)
    assert not np.array_equal(a["edge_pins"], c["edge_pins"])

    def edges(x):
        off = x["edge_off"]
        return sorted((int(x["edge_pins"][off[e]]),
                       tuple(sorted(x["edge_pins"][off[e] + 1: off[e + 1]])),
                       float(x["edge_w"][e]), int(x["edge_nsrc"][e]))
                      for e in range(len(off) - 1))
    assert edges(a) == edges(c) == edges(g)
    parts = np.arange(256) // 32
    assert reference.audit(a, parts, 256, 256) == \
        reference.audit(g, parts, 256, 256)


def test_pack_in_order_breaks_delta():
    cfg = harness.config("snn_rand_truenorth")
    g = graphs.shuffle_edges(graphs.build(cfg, 1024), 3)
    aud = reference.audit(g, reference.pack_in_order(g, cfg["omega"]),
                          cfg["omega"], cfg["delta"])
    assert aud["max_block"] <= cfg["omega"]
    assert aud["max_inbound"] > cfg["delta"]


def _small_caps():
    from repro.core.hypergraph import Caps
    g = graphs.snn_smallworld(64, 8, 0.35, 0)
    return Caps.for_host(harness.program_graph(g))


def test_kernel_references_agree_with_program_oracles():
    from repro.kernels.gains.ref import gains_ref
    from repro.kernels.pair_scores.ref import pair_scores_ref
    from repro.kernels.pins_count.ref import pins_count_ref

    x = kernels.inputs(_small_caps(), 8, 4)
    mine = kernels.reference_outputs(x)
    ps, gn, pc = x["pair_scores"], x["gains"], x["pins_count"]
    eta, inter = pair_scores_ref(ps["nbr"], ps["trav_m"], ps["trav_w"],
                                 ps["trav_d"])
    np.testing.assert_array_equal(mine["pair_scores"]["eta"],
                                  np.asarray(eta))
    np.testing.assert_array_equal(mine["pair_scores"]["inter"],
                                  np.asarray(inter))
    np.testing.assert_array_equal(
        mine["gains"]["conn"],
        np.asarray(gains_ref(gn["inc"], gn["w"], gn["pins_nz"], gn["h"])))
    pins, pins_in = pins_count_ref(pc["parts"], pc["dst"], pc["kdim"])
    np.testing.assert_array_equal(mine["pins_count"]["pins"],
                                  np.asarray(pins))
    np.testing.assert_array_equal(mine["pins_count"]["pins_in"],
                                  np.asarray(pins_in))


def test_program_kernels_match_and_bfloat16_control_does_not():
    x = kernels.inputs(_small_caps(), 8, 5)
    sound = kernels.errors(x, kernels.program_outputs(x))
    assert sound == dict(gains_err=0.0, pair_scores_err=0.0,
                         pins_count_err=0.0)
    ctl = kernels.errors(x, kernels.reference_outputs(
        x, dtype=ml_dtypes.bfloat16))
    assert ctl["gains_err"] > 0 and ctl["pair_scores_err"] > 0


@pytest.mark.parametrize("gone", [
    "repro.kernels.gains.ops.h_bound",
    "repro.kernels.pins_count.kernel.pins_count_pallas",
])
def test_a_kernel_the_program_no_longer_has_is_left_out(monkeypatch, capsys,
                                                          gone):
    """A kernel renamed or moved in the program leaves its check out; the
    other kernels are still checked and still match."""
    monkeypatch.delattr(gone)
    x = kernels.inputs(_small_caps(), 8, 6)
    errs = kernels.errors(x, kernels.program_outputs(x))
    name = gone.split(".")[2]
    assert f"{name}_err" not in errs
    assert errs == {k: 0.0 for k in ("gains_err", "pair_scores_err",
                                     "pins_count_err") if k != f"{name}_err"}
    assert f"kernel check: {name} left out" in capsys.readouterr().err
