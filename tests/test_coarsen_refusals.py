"""Which limit refuses each coarsening merge: the Omega-bound solve at a
small size (a Loihi-like core, Delta vacuous), and the per-level counts of
neighbour slots refused by Omega and by Delta (`coarsen.propose`) against a
plain numpy recount of the step's candidates."""
import numpy as np
import pytest

from repro.core import generate
from repro.core import hypergraph as H
from repro.core.coarsen import CoarsenParams, coarsen_step
from repro.core.contract import contract
from repro.core.partitioner import partition
from repro.obs import trace as otrace

# 256 neurons of the small-world family on cores of Omega 128, Delta 512:
# 256 axons can never exceed 512 distinct inbound, so only Omega binds
OMEGA_BOUND = dict(n_nodes=256, fanout=16, seed=7)
OMEGA, DELTA = 128, 512


@pytest.fixture(scope="module")
def omega_bound():
    hg = generate.snn_smallworld(**OMEGA_BOUND)
    otrace.reset()
    res = partition(hg, OMEGA, DELTA, theta=4)
    return hg, res, otrace.last_root("partition")


def _plain_audit(hg, parts):
    """Blocks, sizes, distinct inbound hyperedges per block and
    connectivity, recomputed edge by edge."""
    parts = np.asarray(parts, np.int64)
    k = int(parts.max()) + 1
    inbound = [set() for _ in range(k)]
    conn = 0.0
    for e in range(hg.n_edges):
        pins = hg.edge(e)
        for v in pins[hg.edge_nsrc[e]:]:
            inbound[parts[v]].add(e)
        conn += hg.edge_w[e] * (len(set(parts[pins].tolist())) - 1)
    return dict(sizes=np.bincount(parts, minlength=k),
                inbound=np.array([len(s) for s in inbound]), conn=conn)


def test_omega_bound_solve_passes_the_plain_audit(omega_bound):
    hg, res, _ = omega_bound
    assert res.parts.shape == (hg.n_nodes,) and res.parts.min() >= 0
    a = _plain_audit(hg, res.parts)
    assert a["sizes"].max() <= OMEGA
    assert a["inbound"].max() <= DELTA
    assert res.connectivity == pytest.approx(a["conn"], abs=0)
    assert res.n_parts == len(a["sizes"]) == 2


def test_omega_bound_solve_beats_in_order_packing(omega_bound):
    hg, res, _ = omega_bound
    packed = _plain_audit(hg, np.arange(hg.n_nodes) // OMEGA)  # in order
    assert packed["sizes"].max() <= OMEGA  # a valid answer here
    assert res.connectivity <= packed["conn"]


def test_omega_bound_levels_count_only_omega(omega_bound):
    """Delta cannot bind (Delta >= the edge count), so no slot is refused
    for it, while Omega refuses slots near the coarsest levels; the
    ``coarsen_level`` spans carry the counts of `LevelStats`."""
    hg, res, root = omega_bound
    assert DELTA >= hg.n_edges
    coarse = res.level_stats[:-1]
    assert all(s.refused_delta == 0 for s in coarse)
    assert sum(s.refused_omega for s in coarse) > 0
    spans = root.find("coarsen").children
    levels = [s for s in spans if s.name == "coarsen_level"]
    assert len(levels) >= res.n_levels
    for s, ls in zip(levels, coarse):
        assert (s.attrs["slots_live"], s.attrs["refused_omega"],
                s.attrs["refused_delta"]) == \
            (ls.nbr_entries, ls.refused_omega, ls.refused_delta)
    for s in levels:
        assert 0 <= s.attrs["refused_omega"] + s.attrs["refused_delta"] \
            <= s.attrs["slots_live"]


def test_omega_at_least_the_node_count_refuses_nothing_for_it():
    """With Omega >= the node count no merge can break it: every level's
    refusals are Delta's."""
    hg = generate.snn_smallworld(n_nodes=128, fanout=8, seed=3)
    res = partition(hg, hg.n_nodes, 24, theta=2)
    coarse = res.level_stats[:-1]
    assert coarse and all(s.refused_omega == 0 for s in coarse)
    assert sum(s.refused_delta for s in coarse) > 0


def _recount(hg, size, nin, omega, delta):
    """Ordered neighbour pairs (n, m), n != m sharing a hyperedge, whose
    merged size exceeds omega, and of the rest those whose inbound union
    |in(n)| + |in(m)| - |in(n) & in(m)| exceeds delta."""
    nbr, inter = set(), {}
    for e in range(hg.n_edges):
        pins = hg.edge(e).tolist()
        dst = set(hg.dst(e).tolist())
        for a in pins:
            for b in pins:
                if a != b:
                    nbr.add((a, b))
                    if a in dst and b in dst:
                        inter[(a, b)] = inter.get((a, b), 0) + 1
    over_omega = over_delta = 0
    for a, b in nbr:
        if size[a] + size[b] > omega:
            over_omega += 1
        elif nin[a] + nin[b] - inter.get((a, b), 0) > delta:
            over_delta += 1
    return len(nbr), over_omega, over_delta


@pytest.mark.parametrize("omega,delta,level", [
    (6, 20, 2),      # both limits refuse slots at this level
    (8, 1000, 3),    # Delta vacuous
    (1000, 20, 1),   # Omega vacuous
])
def test_refusals_equal_a_numpy_recount(omega, delta, level):
    hg = generate.snn_smallworld(n_nodes=96, fanout=6, seed=5)
    caps = H.Caps.for_host(hg)
    d = H.device_from_host(hg, caps)
    params = CoarsenParams(omega=omega, delta=delta)
    for _ in range(level):
        match, n_pairs, _ = coarsen_step(d, caps, params)
        assert int(n_pairs) > 0
        d, _ = contract(d, match, caps)
    _, _, props = coarsen_step(d, caps, params)
    n = int(d.n_nodes)
    host = H.host_from_device(d)
    # the step's inputs: cluster sizes, and |in(n)| as the program keeps it,
    # checked against the level's own hyperedges
    size = np.asarray(d.node_size)[:n]
    nin = np.asarray(d.node_nin)[:n]
    recount_nin = np.zeros(n, np.int64)
    for e in range(host.n_edges):
        recount_nin[host.dst(e)] += 1
    np.testing.assert_array_equal(nin, recount_nin)
    slots, over_omega, over_delta = _recount(host, size, nin, omega, delta)
    assert int(props.n_nbr_entries) == slots
    assert (int(props.n_refused_omega), int(props.n_refused_delta)) == \
        (over_omega, over_delta)
    if omega < 1000 and delta < 1000:
        assert over_omega > 0 and over_delta > 0
    if delta >= 1000:
        assert over_delta == 0
    if omega >= 1000:
        assert over_omega == 0
