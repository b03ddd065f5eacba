"""``coarsen_levels``, ``omega_refused_pct`` and ``delta_refused_pct`` on
hand-made span trees with known answers, on the trees of a program that
does not count refusals, and on real CPU solves of the program."""
import pytest

import harness
import spans
from repro.obs import trace as otrace

NAMES = ("coarsen_levels", "omega_refused_pct", "delta_refused_pct")


def _sp(name, t0, t1, *children, **attrs):
    return otrace.Span(name=name, attrs=attrs, t0=t0, t1=t1,
                       children=list(children))


def _tree(base, levels=((100, 3, 7), (40, 5, 0), (10, 2, 1)),
          counted=True):
    """One solve whose coarsening entered one level per ``(slots_live,
    refused_omega, refused_delta)``; ``counted`` False leaves the counts
    out, as a program without them does."""
    def level(i, live, om, de):
        attrs = dict(slots_live=live, refused_omega=om,
                     refused_delta=de) if counted else {}
        return _sp("coarsen_level", base + 0.1 * i, base + 0.1 * (i + 1),
                   level=i, **attrs)

    return _sp("partition", base, base + 1.0,
               _sp("coarsen", base, base + 0.5,
                   *(level(i, *lv) for i, lv in enumerate(levels))),
               _sp("refine", base + 0.5, base + 0.9))


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


def _read(name, run):
    return harness.metric_reader(name)(run)


@pytest.mark.parametrize("name,one,both", [
    # one: the first tree alone; both: with a second tree of two levels,
    # (50, 0, 20) and (50, 10, 0)
    ("coarsen_levels", 3.0, 2.5),
    ("omega_refused_pct", 100.0 * 10 / 150, 100.0 * 20 / 250),
    ("delta_refused_pct", 100.0 * 8 / 150, 100.0 * 28 / 250),
])
def test_known_answers(retained, name, one, both):
    a = _tree(10.0)
    b = _tree(20.0, levels=((50, 0, 20), (50, 10, 0)))
    retained(a)
    assert _read(name, Run([a])) == pytest.approx(one)
    retained(a, b)
    assert _read(name, Run([a, b])) == pytest.approx(both)


@pytest.mark.parametrize("name", NAMES[1:])
@pytest.mark.parametrize("which", ["all", "one"])
def test_spans_without_the_counts_read_nothing(retained, name, which):
    if which == "all":
        a = _tree(10.0, counted=False)
    else:
        a = _tree(10.0)
        a.children[0].children.append(_tree(10.0, levels=((5, 0, 0),),
                                             counted=False)
                                       .children[0].children[0])
    retained(a)
    assert spans.trees(Run([a])) == [a]
    assert _read(name, Run([a])) is None
    # the level count needs no counts
    assert _read("coarsen_levels", Run([a])) is not None


@pytest.mark.parametrize("name", NAMES)
def test_no_solves_read_nothing(retained, name):
    retained(_tree(10.0))
    assert _read(name, Run([])) is None


@pytest.mark.parametrize("name", NAMES)
def test_trees_that_are_not_the_window_read_nothing(retained, name):
    a = _tree(10.0)
    retained(a)
    r = Run([a])
    r.solves[0]["spans"]["coarsen"] += 1.0
    assert _read(name, r) is None


@pytest.mark.parametrize("omega,delta,binds", [
    (24, 96, "omega"),   # 96 axons: delta cannot bind
    (96, 24, "delta"),   # omega holds every neuron
])
def test_real_solves(omega, delta, binds):
    """CPU solves of the program through ``run.record``: the shares equal
    the levels' own counts, and only the limit that can bind refuses."""
    import graphs
    import run

    cfg = dict(harness.config("snn_rand_truenorth"), n_nodes=96,
               omega=omega, delta=delta, theta=4)
    hg = harness.program_graph(graphs.requests(cfg, dict(scales=[1.0]),
                                               7)[0])
    otrace.reset()
    res = run.solve(hg, cfg)
    r = Run([])
    r.solves = [run.record(res, 0.0)]
    levels = spans.named(spans.trees(r), "coarsen_level")[0]
    live = sum(s.attrs["slots_live"] for s in levels)
    pct = {k: 100.0 * sum(s.attrs[f"refused_{k}"] for s in levels) / live
           for k in ("omega", "delta")}
    assert _read("coarsen_levels", r) == len(levels) >= res.n_levels
    for k in ("omega", "delta"):
        assert _read(f"{k}_refused_pct", r) == pytest.approx(pct[k])
    assert pct[binds] > 0
    assert pct[{"omega": "delta", "delta": "omega"}[binds]] == 0
