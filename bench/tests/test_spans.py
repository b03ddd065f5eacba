"""The window's span trees (``spans.py``) and the five readers built on
them, on a hand-made span tree and trace with known answers, and on real
CPU solves of the program."""
import pytest

import harness
import spans
from repro.obs import trace as otrace

MS = 1_000_000  # ns
READERS = ("host_syncs", "sync_wait_s", "sync_idle_s", "refine_applied_pct",
           "refine_noop_pct")


def _sp(name, t0, t1, *children, **attrs):
    return otrace.Span(name=name, attrs=attrs, t0=t0, t1=t1,
                       children=list(children))


def _tree(base=100.0, syncs=True, counts=True):
    """One solve: ``partition`` over [base, base + 1) s on the program's
    clock, four ``device_sync`` spans and two counted ``refine_level``s."""
    def sync(a, b, what):
        return [_sp("device_sync", base + a, base + b, what=what)] \
            if syncs else []

    def level(lvl, a, b, proposed, applied, noop):
        attrs = dict(moves_proposed=proposed, moves_applied=applied,
                     noop_reps=noop, reps=4) if counts else {}
        return _sp("refine_level", base + a, base + b, level=lvl, **attrs)

    return _sp(
        "partition", base, base + 1.0,
        _sp("setup", base, base + 0.1),
        _sp("coarsen", base + 0.1, base + 0.4,
            *sync(0.10, 0.15, "coarsen_nodes"),
            _sp("coarsen_level", base + 0.15, base + 0.35,
                *sync(0.30, 0.35, "level_scalars"), level=0),
            *sync(0.35, 0.40, "coarsen_tail")),
        _sp("refine", base + 0.4, base + 0.9,
            level(1, 0.4, 0.6, 10, 4, 1), level(0, 0.6, 0.9, 30, 6, 3)),
        _sp("audit", base + 0.9, base + 1.0, *sync(0.95, 1.0, "parts")))


def _record(root):
    return dict(spans={c.name: c.duration for c in root.children})


def _trace(root, start=5000 * MS, skew=0):
    """The trace of one solve: its ``partition`` event at ``start`` ns, the
    spans the plain trace keeps (no ``device_sync``) on the same offset,
    ``skew`` ns late on ``audit``, and three device operations."""
    off = start - root.t0 * 1e9
    host = [["bench_window", 4900 * MS, 1200 * MS]]
    for s in spans.walk(root):
        if s.name != "device_sync":
            late = skew if s.name == "audit" else 0
            host.append([s.name, s.t0 * 1e9 + off + late,
                         (s.t1 - s.t0) * 1e9])
    ops = [["%fusion.1 = f32[8]{0} fusion()", 5000 * MS, 120 * MS],
           ["%fusion.2 = f32[8]{0} fusion()", 5160 * MS, 160 * MS],
           ["%fusion.3 = f32[8]{0} fusion()", 5400 * MS, 560 * MS]]
    return dict(device={"/device:TPU:0": ops}, modules={}, host=host)


class Run:
    def __init__(self, roots, trace=None, recs=None):
        self.solves = recs if recs is not None else [_record(r)
                                                     for r in roots]
        self.trace = trace


@pytest.fixture
def retained(monkeypatch):
    """Stand the given trees in for the program's retained roots."""
    def use(*roots):
        monkeypatch.setattr(otrace, "roots", lambda: list(roots))
    return use


def _read(name, run):
    return harness.metric_reader(name)(run)


def test_trees_are_the_window_solves(retained):
    old, a, b = _tree(10.0), _tree(20.0), _tree(30.0)
    retained(old, _sp("service.solve", 25.0, 26.0), a, b)
    assert spans.trees(Run([a, b])) == [a, b]
    assert spans.trees(Run([b])) == [b]
    retained(b)
    assert spans.trees(Run([a, b])) is None    # a solve missing


def test_a_mismatch_reads_nothing(retained):
    a, b = _tree(10.0), _tree(20.0)
    retained(a, b)
    recs = [_record(a), _record(b)]
    recs[0]["spans"]["refine"] += 1e-9          # not this solve's tree
    run = Run([a, b], trace=_trace(b), recs=recs)
    assert spans.trees(run) is None
    assert all(_read(n, run) is None for n in READERS)


def test_host_syncs_and_wait(retained, capsys):
    a, b = _tree(10.0), _tree(20.0)
    retained(a, b)
    assert _read("host_syncs", Run([a, b])) == 4
    assert "coarsen_nodes 1, level_scalars 1" in capsys.readouterr().err
    assert _read("sync_wait_s", Run([a, b])) == pytest.approx(0.2)


def test_a_program_without_the_spans_reads_nothing(retained):
    """The parent program: no ``device_sync`` span, no move counters."""
    root = _tree(syncs=False, counts=False)
    retained(root)
    run = Run([root], trace=_trace(root))
    assert spans.trees(run) == [root]
    assert all(_read(n, run) is None for n in READERS)


def test_sync_idle_on_the_trace_clock(retained, capsys):
    root = _tree()
    retained(root)
    # syncs at 5100-5150, 5300-5350, 5350-5400, 5950-6000 ms against idle
    # 5120-5160, 5320-5400, 5960-6100: 30 + 30 + 50 + 40 ms
    run = Run([root], trace=_trace(root, skew=2000))
    assert _read("sync_idle_s", run) == pytest.approx(0.150, abs=1e-9)
    assert "largest gap from the trace's span events 0.0020 ms" in \
        capsys.readouterr().err


def test_placement_and_residual():
    root = _tree()
    trace = _trace(root, skew=3000)
    placed = spans.on_trace_clock([root], trace)
    (first, a, b), = [p for p in placed[0] if p[0].name == "partition"]
    assert (a, b) == pytest.approx((5000 * MS, 6000 * MS))
    assert spans.residual_ns(placed, trace) == pytest.approx(3000, abs=1)
    assert spans.on_trace_clock([root, _tree(50.0)], trace) is None
    lost = next(h for h in trace["host"] if h[0] == "refine_level")
    trace["host"].remove(lost)                 # one of two levels lost
    assert spans.residual_ns(placed, trace) is None


def test_refine_counters(retained, capsys):
    a, b = _tree(10.0), _tree(20.0)
    retained(a, b)
    # per solve: proposed 10 + 30, applied 4 + 6, no-op reps 1 + 3 of 8
    assert _read("refine_applied_pct", Run([a, b])) == pytest.approx(25.0)
    assert "1: 10 4 1/4, 0: 30 6 3/4" in capsys.readouterr().err
    assert _read("refine_noop_pct", Run([a, b])) == pytest.approx(50.0)


def test_real_solves(capsys):
    """Two CPU solves of the program through ``run.record``: the readers
    find the trees, and every solve reads the same sync count."""
    import graphs
    import run

    cfg = dict(harness.config("snn_rand_truenorth"), n_nodes=96, omega=24,
               delta=96, theta=4)
    g = graphs.requests(cfg, dict(scales=[1.0]), 7)[0]
    hg = harness.program_graph(g)
    otrace.reset()
    solves = [run.record(run.solve(hg, cfg), 0.0) for _ in range(2)]
    r = Run([], recs=solves)
    roots = spans.trees(r)
    assert roots is not None and len(roots) == 2
    n = [len(t) for t in spans.named(roots, "device_sync")]
    assert n[0] == n[1] > 0
    assert _read("host_syncs", r) == n[0]
    assert 0 <= _read("refine_applied_pct", r) <= 100
    assert 0 <= _read("refine_noop_pct", r) <= 100
