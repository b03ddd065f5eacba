"""Telemetry layer: registry semantics, span tree, watchdog counters,
per-level V-cycle stats, and the bit-exactness contract (telemetry on/off
must not change any computed result)."""
import json
import threading

import jax
import numpy as np
import pytest

from repro.core import generate
from repro.core.partitioner import partition
from repro.dist.ft import StepWatchdog
from repro.obs import metrics as obs_metrics
from repro.obs import trace as otrace


# ------------------------------------------------------------------ registry
def test_registry_counter_gauge_labeled_series():
    r = obs_metrics.Registry()
    r.counter("c", route="a")
    r.counter("c", 2.0, route="a")
    r.counter("c", route="b")
    r.gauge("g", 7.5, k="x")
    assert r.value("c", route="a") == 3.0
    assert r.value("c", route="b") == 1.0
    assert r.value("c", route="missing") == 0.0
    assert r.total("c") == 4.0
    assert r.value("g", k="x") == 7.5
    # label order must not split series
    r.counter("c2", a="1", b="2")
    r.counter("c2", b="2", a="1")
    assert r.value("c2", a="1", b="2") == 2.0


def test_registry_zero_preregisters_series():
    r = obs_metrics.Registry()
    r.counter("c", 0, route="bucket")
    snap = r.snapshot()
    assert snap["counters"]["c"] == [
        dict(labels=dict(route="bucket"), value=0.0)]


def test_registry_histogram_bucket_edges():
    r = obs_metrics.Registry()
    # edges fixed at first observation; +inf appended automatically
    r.observe("h", 0.5, buckets=(1.0, 2.0))
    r.observe("h", 1.0)    # on-edge lands in the <= 1.0 bucket
    r.observe("h", 1.5)
    r.observe("h", 99.0)   # overflow lands in +inf
    (s,) = r.snapshot()["histograms"]["h"]
    assert s["edges"] == [1.0, 2.0, "inf"]
    assert s["counts"] == [2, 1, 1]
    assert s["count"] == 4 and s["sum"] == pytest.approx(102.0)


def test_registry_series_overflow_collapses_not_crashes():
    r = obs_metrics.Registry(max_series=4)
    for i in range(10):
        r.counter("c", rid=i)
        r.observe("h", float(i), rid=i)
    snap = r.snapshot()
    assert len(snap["counters"]["c"]) <= 5  # 4 real + 1 overflow
    labels = [s["labels"] for s in snap["counters"]["c"]]
    assert {"overflow": "true"} in labels
    assert r.total("obs.series_overflow") > 0
    assert r.total("c") == 10.0  # no event dropped, only labels collapsed


def test_registry_thread_safety_hammering():
    r = obs_metrics.Registry()
    n_threads, n_iters = 8, 500

    def hammer(tid):
        for i in range(n_iters):
            r.counter("c", worker=tid % 2)
            r.gauge("g", float(i), worker=tid % 2)
            r.observe("h", 0.01 * (i % 7), worker=tid % 2)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert r.total("c") == n_threads * n_iters
    hists = r.snapshot()["histograms"]["h"]
    assert sum(s["count"] for s in hists) == n_threads * n_iters


def test_registry_jsonl_and_prometheus_goldens():
    r = obs_metrics.Registry()
    r.counter("svc.reqs", 3, route="bucket")
    r.gauge("svc.pending", 2)
    r.observe("svc.lat.s", 0.02, buckets=(0.01, 0.1))
    r.observe("svc.lat.s", 0.2)
    lines = [json.loads(ln) for ln in r.to_jsonl().splitlines()]
    assert lines == [
        dict(kind="counter", name="svc.reqs",
             labels=dict(route="bucket"), value=3.0),
        dict(kind="gauge", name="svc.pending", labels={}, value=2.0),
        dict(kind="histogram", name="svc.lat.s", labels={},
             edges=[0.01, 0.1, "inf"], counts=[0, 1, 1],
             sum=pytest.approx(0.22), count=2),
    ]
    assert r.render() == (
        "# TYPE svc_reqs counter\n"
        'svc_reqs{route="bucket"} 3\n'
        "# TYPE svc_pending gauge\n"
        "svc_pending 2\n"
        "# TYPE svc_lat_s histogram\n"
        'svc_lat_s_bucket{le="0.01"} 0\n'
        'svc_lat_s_bucket{le="0.1"} 1\n'
        'svc_lat_s_bucket{le="+Inf"} 2\n'
        "svc_lat_s_sum 0.22\n"
        "svc_lat_s_count 2\n")


def test_registry_reset_and_dump_json(tmp_path):
    r = obs_metrics.Registry()
    r.counter("c")
    path = tmp_path / "m.json"
    doc = obs_metrics.dump_json(str(path), r)
    loaded = json.loads(path.read_text())
    assert loaded["metrics"]["counters"]["c"][0]["value"] == 1.0
    assert set(doc) == {"ts", "metrics", "spans"}
    r.reset()
    assert r.snapshot() == dict(counters={}, gauges={}, histograms={})


# --------------------------------------------------------------------- spans
def test_span_tree_nesting_and_attribution():
    otrace.reset()
    with otrace.span("outer", level=0) as sp_out:
        with otrace.span("inner_a") as sp_a:
            pass
        with otrace.span("inner_b"):
            pass
    assert sp_out.t1 is not None
    assert [c.name for c in sp_out.children] == ["inner_a", "inner_b"]
    assert sp_out.find("inner_b") is sp_out.children[1]
    assert sp_out.duration >= sp_a.duration
    # self time excludes children; all non-negative
    assert 0 <= sp_out.self_time <= sp_out.duration
    assert otrace.last_root("outer") is sp_out
    agg = {a["name"]: a for a in otrace.aggregate()}
    assert agg["outer"]["count"] == 1 and agg["inner_a"]["count"] == 1
    assert agg["outer"]["total_s"] == pytest.approx(sp_out.duration)


def test_span_sync_blocks_device_value():
    with otrace.span("devwork") as sp:
        x = sp.sync(jax.numpy.arange(8) * 2)
    assert sp._sync is None  # drained at exit
    np.testing.assert_array_equal(np.asarray(x), np.arange(8) * 2)


def test_span_observes_metrics_registry():
    obs_metrics.REGISTRY.reset()
    with otrace.span("phasex"):
        pass
    hists = obs_metrics.REGISTRY.snapshot()["histograms"]
    assert "span.phasex.s" in hists
    assert hists["span.phasex.s"][0]["count"] == 1


def test_span_roots_bounded():
    otrace.reset()
    for i in range(otrace.MAX_ROOTS + 10):
        with otrace.span("r", i=i):
            pass
    assert len(otrace.roots()) == otrace.MAX_ROOTS
    assert otrace.roots()[-1].attrs["i"] == otrace.MAX_ROOTS + 9


def test_device_sync_span_times_the_read():
    x = jax.numpy.arange(8) * 3
    with otrace.span("outer") as sp:
        got = otrace.device_sync(x, "probe")
        same = otrace.device_sync(x, "probe_block", jax.block_until_ready)
    assert isinstance(got, np.ndarray)
    np.testing.assert_array_equal(got, np.arange(8) * 3)
    assert same is x
    assert [(c.name, c.attrs) for c in sp.children] == [
        ("device_sync", dict(what="probe")),
        ("device_sync", dict(what="probe_block"))]


def test_span_enters_no_named_scope(monkeypatch):
    """Host spans leave op names to the scopes inside the jitted steps."""
    def refuse(name):
        raise AssertionError(f"span entered named_scope({name!r})")

    monkeypatch.setattr(jax, "named_scope", refuse)
    with otrace.span("no_scope_outer"):
        with otrace.span("no_scope_inner"):
            pass


# ------------------------------------------------------------------ watchdog
def test_watchdog_bounded_fired_steps_and_reset():
    fired = []
    ev = threading.Event()

    def on_stall(step):
        fired.append(step)
        ev.set()

    wd = StepWatchdog(0.0, on_stall, max_fired=4)  # fires immediately
    for step in range(10):
        ev.clear()
        with wd.watch(step):
            assert ev.wait(5.0), f"watchdog never fired for step {step}"
    assert len(wd.fired_steps) == 4  # bounded deque kept the newest
    assert list(wd.fired_steps) == [6, 7, 8, 9]
    assert fired == list(range(10))
    wd.reset()
    assert not wd.fired_steps
    wd.stop()


def test_watchdog_registry_counters_and_stall_histogram():
    r = obs_metrics.Registry()
    ev = threading.Event()
    wd = StepWatchdog(0.05, lambda s: ev.set(), registry=r)
    with wd.watch(0):
        assert ev.wait(5.0)
    wd.stop()
    assert r.total("watchdog.stalls") == 1.0
    (h,) = r.snapshot()["histograms"]["watchdog.stall.s"]
    assert h["count"] == 1 and h["sum"] >= 0.05


# ----------------------------------------------------- partitioner telemetry
HG = generate.snn_smallworld(n_nodes=96, fanout=6, seed=3)
OM, DL = 24, 96


def test_partition_timings_is_span_view():
    """Transition contract: the legacy timings dict is a thin view over the
    span tree — identical floats, not merely close."""
    res = partition(HG, omega=OM, delta=DL, theta=4)
    root = otrace.last_root("partition")
    assert root is not None
    assert res.timings["total"] == root.duration
    assert res.timings["coarsen"] == root.find("coarsen").duration
    assert res.timings["refine"] == root.find("refine").duration
    assert {c.name for c in root.children} >= {"setup", "coarsen",
                                               "refine", "audit"}
    n_rl = len([s for s in root.find("refine").children
                if s.name == "refine_level"])
    assert n_rl == res.n_levels + 1


def test_partition_level_stats_structural():
    res = partition(HG, omega=OM, delta=DL, theta=4)
    ls = res.level_stats
    assert len(ls) == res.n_levels + 1
    assert ls[0].level == 0 and ls[0].nodes == HG.n_nodes
    assert ls[0].edges == HG.n_edges and ls[0].pins == HG.n_pins
    # node counts shrink as the V-cycle coarsens
    for a, b in zip(ls, ls[1:]):
        assert b.nodes <= a.nodes
    for s in ls[:-1]:
        assert s.pairs_live is not None and 0 <= s.pair_occupancy <= 1
        assert s.nbr_entries is not None and 0 <= s.nbr_occupancy <= 1
        assert s.kernel_coarsen in (0, 1)
    for s in ls:
        assert s.kernel_refine is not None
        assert s.connectivity is None  # quality gated off by default
    d = ls[0].to_dict()
    assert d["level"] == 0 and "pair_occupancy" in d


def test_partition_collect_stats_quality_matches_audit():
    res = partition(HG, omega=OM, delta=DL, theta=4, collect_stats=True)
    ls = res.level_stats
    for s in ls:
        assert s.connectivity is not None and s.cut_net is not None
        assert s.max_size is not None and s.max_size <= OM
        assert s.size_slack == OM - s.max_size
        assert s.max_inbound is not None and s.max_inbound <= DL
        assert s.inbound_slack == DL - s.max_inbound
    # level 0 quality is the final partition: must equal the host audit
    assert ls[0].connectivity == pytest.approx(res.connectivity)
    assert ls[0].cut_net == pytest.approx(res.cut_net)
    assert ls[0].max_size == res.audit["max_size"]


def test_partition_telemetry_parity_bit_exact():
    """The bit-exactness contract: collect_stats on/off (and spans, which
    are always on) change no computed result."""
    base = partition(HG, omega=OM, delta=DL, theta=4)
    stats = partition(HG, omega=OM, delta=DL, theta=4, collect_stats=True)
    np.testing.assert_array_equal(base.parts, stats.parts)
    assert base.connectivity == stats.connectivity
    assert base.cut_net == stats.cut_net
    assert base.audit == stats.audit


def test_kway_timings_and_level_stats():
    from repro.core.kway import partition_kway
    res = partition_kway(HG, k=4, theta=4, collect_stats=True)
    root = otrace.last_root("partition_kway")
    assert res.timings["total"] == root.duration
    assert root.find("initial_kway") is not None
    ls = res.level_stats
    assert len(ls) == res.n_levels + 1
    assert ls[0].connectivity == pytest.approx(res.connectivity)
    # Delta is +inf in k-way mode: inbound slack still finite/meaningful
    assert ls[0].max_inbound is not None


def _syncs(root):
    out = []

    def walk(sp):
        if sp.name == "device_sync":
            out.append(sp.attrs["what"])
        for c in sp.children:
            walk(c)
    walk(root)
    return out


def test_partition_device_syncs_match_the_code():
    """Every blocking host read of a solve is one ``device_sync`` span, in
    the order a solve makes them: per coarsening-loop test one node
    count, per coarsening level entered one batched scalar read, then the
    coarsening tail, the coarsest pair audit, its three structure reads,
    the refinement tail, the one batched refinement readback and the
    parts."""
    res = partition(HG, omega=OM, delta=DL, theta=4)
    root = otrace.last_root("partition")
    coarsen = root.find("coarsen")
    entered = [c for c in coarsen.children if c.name == "coarsen_level"]
    assert len(entered) in (res.n_levels, res.n_levels + 1)  # + a break
    loop = []
    for lvl in range(len(entered)):
        loop += ["coarsen_nodes", "level_scalars"]
    if len(entered) == res.n_levels:   # stopped by the loop test
        loop.append("coarsen_nodes")
    expect = loop + ["coarsen_tail", "pair_count", "coarsest_nodes",
                     "coarsest_edges", "coarsest_pins", "refine_tail",
                     "refine_readback", "parts"]
    assert _syncs(root) == expect
    assert len(expect) == 2 * res.n_levels + 9 + (len(entered)
                                                  - res.n_levels)
    assert obs_metrics.REGISTRY.snapshot()["histograms"][
        "span.device_sync.s"]


def test_refine_from_device_syncs():
    res = partition(HG, omega=OM, delta=DL, theta=4)
    from repro.core.partitioner import refine_from
    refine_from(HG, res.parts, OM, DL, theta=4)
    assert _syncs(otrace.last_root("partition")) == [
        "refine_tail", "refine_readback", "parts"]


@pytest.mark.parametrize("entry", ["partition", "refine_from", "kway"])
def test_setup_blocks_on_its_upload(entry, monkeypatch):
    """The upload drains inside the ``setup`` span, so it is timed as
    set-up and not in the first coarsening read."""
    from repro.core.kway import partition_kway
    from repro.core.partitioner import refine_from

    seen = []
    real = jax.block_until_ready

    def recording(x):
        st = otrace._stack()
        seen.append((st[-1].name if st else None,
                     {type(v).__name__ for v in jax.tree.leaves(x)}))
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", recording)
    if entry == "partition":
        partition(HG, omega=OM, delta=DL, theta=2)
    elif entry == "refine_from":
        refine_from(HG, np.arange(HG.n_nodes) % 4, OM, DL, theta=2)
    else:
        partition_kway(HG, k=4, theta=2)
    setup = [leaves for name, leaves in seen if name == "setup"]
    assert len(setup) == 1 and setup[0] == {"ArrayImpl"}


def test_refine_counters_match_the_rep_log():
    """The per-level counters summed on the device equal a recount of
    ``collect_log``'s per-repetition reads, level by level."""
    theta = 4
    res = partition(HG, omega=OM, delta=DL, theta=theta, collect_log=True)
    reps = [e for e in res.level_log if "rep" in e]
    assert len(reps) == theta * (res.n_levels + 1)
    root = otrace.last_root("partition")
    spans = [s for s in root.find("refine").children
             if s.name == "refine_level"]
    by_level = {s.level: s for s in res.level_stats}
    for i, sp in enumerate(spans):        # coarsest level first
        log = reps[i * theta:(i + 1) * theta]
        ls = by_level[sp.attrs["level"]]
        want = dict(moves_proposed=sum(e["proposed"] for e in log),
                    moves_applied=sum(e["applied"] for e in log),
                    skipped_reps=sum(e["skipped"] for e in log),
                    noop_reps=sum(e["applied"] == 0 for e in log))
        assert {k: getattr(ls, k) for k in want} == want
        assert {k: sp.attrs[k] for k in want} == want
        assert sp.attrs["reps"] == theta
        assert ls.kernel_refine == sum(e["kernel"] for e in log)
        assert 0 <= ls.moves_applied <= ls.moves_proposed
    assert sum(s.moves_applied for s in res.level_stats) > 0
    assert sum(s.skipped_reps for s in res.level_stats) > 0


def test_collect_log_parity_bit_exact():
    base = partition(HG, omega=OM, delta=DL, theta=4)
    logged = partition(HG, omega=OM, delta=DL, theta=4, collect_log=True)
    np.testing.assert_array_equal(base.parts, logged.parts)
    assert [s.to_dict() for s in base.level_stats] == \
        [s.to_dict() for s in logged.level_stats]


def _step_text(which):
    """A step's lowered text with locations, at the test graph's shapes."""
    import jax.numpy as jnp

    from repro.core import coarsen, contract, refine
    from repro.core.hypergraph import Caps, device_from_host

    caps = Caps.for_host(HG)
    d = device_from_host(HG, caps)
    if which == "refine_step":
        params = refine.RefineParams(omega=OM, delta=DL, theta=2)
        lowered = refine.refine_step.lower(
            d, jnp.zeros((caps.n,), jnp.int32), jnp.int32(4), caps, 4,
            params, True)
    elif which == "coarsen_step":
        lowered = coarsen.coarsen_step.lower(
            d, caps, coarsen.CoarsenParams(omega=OM, delta=DL))
    else:
        lowered = contract.contract.lower(
            d, jnp.full((caps.n,), -1, jnp.int32), caps)
    return lowered.as_text(debug_info=True)


def test_gated_refine_step_names_its_phases_inside_the_run_branch():
    """`refine_level` calls the step with the previous counts, which puts
    the repetition under a cond: the phases keep their scopes, inside the
    branch that runs it."""
    import jax.numpy as jnp

    from repro.core import refine
    from repro.core.hypergraph import Caps, device_from_host

    caps = Caps.for_host(HG)
    text = refine.refine_step.lower(
        device_from_host(HG, caps), jnp.zeros((caps.n,), jnp.int32),
        jnp.int32(4), caps, 4, refine.RefineParams(omega=OM, delta=DL,
                                                   theta=2),
        True, jnp.zeros(len(refine.REP_COUNTS), jnp.int32),
        True).as_text(debug_info=True)
    for scope in ["pins", "propose", "chains", "inseq_gains", "events"]:
        assert f'"jit(refine_step)/cond/branch_1_fun/{scope}/' in text, scope


@pytest.mark.parametrize("which,scopes", [
    ("refine_step", ["pins", "propose", "chains", "inseq_gains", "events"]),
    ("coarsen_step", ["pairs", "neighbors", "propose", "matching"]),
    ("contract", ["clusters", "pins", "incidence"]),
])
def test_steps_name_their_phases(which, scopes):
    text = _step_text(which)
    for scope in scopes:
        assert f'"jit({which})/{scope}/' in text, scope
    # a host span open while tracing leaves no name in the step
    with otrace.span("host_span_probe"):
        assert "host_span_probe" not in _step_text(which)


@pytest.mark.slow
def test_obs_parity_inprocess_8dev():
    """Forced-8 acceptance: telemetry on/off is bit-identical through the
    mesh-sharded race=False path too."""
    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    from repro.dist.sharding import Plan
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    plan = Plan.make(mesh)
    hg = generate.snn_smallworld(n_nodes=200, fanout=10, seed=7)
    kw = dict(omega=32, delta=128, theta=8, plan=plan, shard_graph=True,
              race=False)
    base = partition(hg, **kw)
    stats = partition(hg, collect_stats=True, **kw)
    np.testing.assert_array_equal(base.parts, stats.parts)
    assert base.audit == stats.audit
    # sharded storage: structural stats present, quality side stays None
    assert stats.level_stats and stats.level_stats[0].nodes == hg.n_nodes
    assert stats.level_stats[0].connectivity is None
