"""``refine_skipped_pct`` on hand-made span trees with known answers, on
the trees of a program that does not count skipped repetitions, and on
real CPU solves of the program."""
import pytest

import harness
import spans
from repro.obs import trace as otrace


def _sp(name, t0, t1, *children, **attrs):
    return otrace.Span(name=name, attrs=attrs, t0=t0, t1=t1,
                       children=list(children))


def _tree(base, skipped=(3, 1)):
    """One solve with two counted ``refine_level``s of four repetitions;
    ``skipped`` None leaves the count out, as a program without the early
    exit does."""
    def level(lvl, a, b, n_skipped):
        attrs = dict(moves_proposed=8, moves_applied=1, noop_reps=3, reps=4)
        if n_skipped is not None:
            attrs["skipped_reps"] = n_skipped
        return _sp("refine_level", base + a, base + b, level=lvl, **attrs)

    lv = skipped if skipped is not None else (None, None)
    return _sp("partition", base, base + 1.0,
               _sp("refine", base + 0.4, base + 0.9,
                   level(1, 0.4, 0.6, lv[0]), level(0, 0.6, 0.9, lv[1])))


class Run:
    def __init__(self, roots):
        self.solves = [dict(spans={c.name: c.duration for c in r.children})
                       for r in roots]
        self.trace = None


@pytest.fixture
def retained(monkeypatch):
    def use(*roots):
        monkeypatch.setattr(otrace, "roots", lambda: list(roots))
    return use


def _read(run):
    return harness.metric_reader("refine_skipped_pct")(run)


def test_share_of_the_window_repetitions(retained):
    a, b = _tree(10.0), _tree(20.0, skipped=(0, 2))
    retained(a, b)
    # skipped 3 + 1 + 0 + 2 of 16 repetitions
    assert _read(Run([a, b])) == pytest.approx(37.5)
    assert _read(Run([b])) == pytest.approx(25.0)


@pytest.mark.parametrize("which", ["all", "one"])
def test_spans_without_the_count_read_nothing(retained, which):
    a = _tree(10.0, skipped=None) if which == "all" else \
        _tree(10.0, skipped=(3, None))
    retained(a)
    assert spans.trees(Run([a])) == [a]
    assert _read(Run([a])) is None


def test_no_solves_read_nothing(retained):
    retained(_tree(10.0))
    assert _read(Run([])) is None


def test_real_solves():
    """CPU solves of the program through ``run.record``: the share lies in
    [0, 100] and equals the levels' own counts."""
    import graphs
    import run

    cfg = dict(harness.config("snn_rand_truenorth"), n_nodes=96, omega=24,
               delta=96, theta=4)
    hg = harness.program_graph(graphs.requests(cfg, dict(scales=[1.0]),
                                               7)[0])
    otrace.reset()
    res = run.solve(hg, cfg)
    r = Run([])
    r.solves = [run.record(res, 0.0)]
    skipped = sum(ls.skipped_reps for ls in res.level_stats)
    assert 0 < skipped
    assert _read(r) == pytest.approx(100.0 * skipped
                                     / (4 * len(res.level_stats)))
