"""Device time by program scope and idle time by ``repro.*`` span
(``bench/scopes.py``), on a hand-made trace whose answers are known and
on a recorded chip trace.

The layout is the one ``trace.py`` documents for a TPU trace: a window
span on a host line, ``XLA Ops`` events on ``/device:TPU:0`` (a
``while`` spanning its body's operations), each op's scope path in its
sixth field, and ``repro.*`` host spans around the gaps.
"""

import gzip
import json
import os

import pytest

from bench import scopes, trace

MS = 1_000_000
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _op(name, a, b, scope):
    return scopes.Event(DEV, trace.OPS_LINE, name, a * MS, (b - a) * MS,
                        scope)


def _host(name, a, b):
    return scopes.Event(HOST, "python", name, a * MS, (b - a) * MS)


@pytest.fixture
def events():
    swap = "jit(_swap_iter)/swap/while/body/search"
    return [
        _host(trace.SPAN, 0, 100),
        _host("repro.fit", 0, 100),
        _host("repro.build", 0, 40),
        _host("repro.swap", 40, 100),
        _host("repro.swap.iter", 45, 100),
        _host("repro.host_read", 90, 95),
        _host("$banditpam.py:880 _swap", 40, 100),
        # BUILD: 10..30 busy
        _op("%pad.1 = ...", 10, 14, "jit(_build_fused)/build/while/body/"
            "search/cond/branch_0_fun/fresh/prep/pad"),
        _op("%pairwise_kernel.3 = ...", 14, 24,
            "jit(_build_fused)/build/while/body/search/cond/branch_0_fun/"
            "fresh/pairwise_kernel"),
        _op("%copy.2 = ...", 24, 27, "jit(_build_fused)/build/while/body/"
            "search/cond/branch_0_fun/pic/dynamic_update_slice"),
        _op("%threefry.1 = ...", 27, 30, ""),
        # SWAP: a while spanning 50..90; inside it a conditional whose
        # branch is a scoped slice and a copy the compiler added (no
        # scope of its own: it takes the conditional's), then two scoped
        # ops
        _op("%while.1 = ...", 50, 90, swap),
        _op("%conditional.2 = ...", 50, 60, swap + "/cond"),
        _op("%slice.4 = ...", 50, 53, swap + "/cond/branch_1_fun/pic/slice"),
        _op("%copy.30 = ...", 53, 60, ""),
        _op("%fusion.7 = ...", 60, 70, swap + "/stats/prep/pad"),
        _op("%swap_g_from_cache_kernel.1 = ...", 70, 85,
            swap + "/stats/swap_g_from_cache_kernel"),
    ]


def test_scopes_sum_innermost_ops_by_each_scope_in_their_path(events):
    got = scopes.reduce(events)
    s = got["scopes"]
    assert s["build"] == pytest.approx(0.017)        # 10..27
    assert s["swap"] == pytest.approx(0.035)         # 50..85, not the while
    assert s["search"] == pytest.approx(0.052)
    assert s["fresh"] == pytest.approx(0.014)
    assert s["prep"] == pytest.approx(0.014)         # 4 + 10 ms
    assert s["pic"] == pytest.approx(0.006)          # 3 + 3 ms
    assert s["stats"] == pytest.approx(0.025)
    assert set(s) == {"build", "swap", "search", "fresh", "prep", "pic",
                      "stats"}
    assert got["unscoped"] == pytest.approx(0.003)   # the top-level op


def test_idle_is_named_by_the_innermost_repro_span(events):
    got = scopes.reduce(events)
    idle = dict(got["idle_spans"])
    # Gaps 0..10, 30..50 and 90..100 (the ``while`` is busy to 90), each
    # named by the span that covers most of it, and of those the
    # shortest (``trace._name_gaps``): 30..50 crosses from BUILD into
    # SWAP, so only repro.fit covers all of it; the host read covers half
    # of 90..100, the iteration all.
    assert idle == pytest.approx({"repro.build": 0.010, "repro.fit": 0.020,
                                  "repro.swap.iter": 0.010})
    assert sum(idle.values()) == pytest.approx(
        trace.reduce(events)["window_s"] - trace.reduce(events)["busy_s"])
    assert all(k.startswith("repro.") for k in idle)


def test_trace_reduce_reads_scoped_events_unchanged(events):
    plain = [trace.Event(*e[:5]) for e in events]
    assert trace.reduce(events) == trace.reduce(plain)


def test_no_window_or_no_device_op_reads_nothing(events):
    assert scopes.reduce([e for e in events if e.name != trace.SPAN]) is None
    assert scopes.reduce([e for e in events if e.plane != DEV]) is None


@pytest.mark.parametrize("path,want", [
    ("jit(_swap_iter)/swap/while/body/search/stats/prep/pad",
     {"swap", "search", "stats", "prep"}),
    ("jit(f)/jit(main)/pad", set()),
    ("", set()),
])
def test_scope_names_are_whole_path_components(path, want):
    assert scopes.scope_names(path) == want


# A recorded chip trace: a warm KMedoids(k=5, solver="banditpam_pp") fit
# of the cell's data cut to n=10,000, traced on a TPU v5 lite, each device
# op's scope path read from the modules XLA dumped in that process
# (``scopes.load_events(path, dump_dir)``).
# Kept: the device's ``XLA Ops`` and ``XLA Modules`` lines, the
# ``repro.*`` host spans and the window span, over the whole traced fit.
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_fit_scopes.json.gz")


@pytest.fixture(scope="module")
def chip():
    with gzip.open(DATA, "rt") as f:
        return [scopes.Event(*e) for e in json.load(f)]


def _leaf_ops(events):
    ops = [(e.start_ns, e.start_ns + e.dur_ns, e) for e in events
           if e.plane.startswith(trace.DEVICE_PREFIX)
           and e.line == trace.OPS_LINE]
    return [o for _, _, o in trace._leaves(ops)]


def test_chip_ops_carry_program_scopes(chip):
    got = scopes.reduce(chip)
    assert set(got["scopes"]) >= {"build", "swap", "search", "fresh", "pic",
                                  "stats", "prep", "top2"}
    leaf_s = sum(o.dur_ns for o in _leaf_ops(chip)) / 1e9
    assert got["unscoped"] <= 0.05 * leaf_s
    s = got["scopes"]
    assert s["build"] + s["swap"] <= leaf_s * (1 + 1e-9)
    assert s["prep"] <= s["fresh"] + s["stats"] + s.get("carry", 0)


def test_chip_pad_is_the_wrappers_operand_padding(chip):
    pads = [o for o in _leaf_ops(chip) if trace.op_name(o.name) == "pad"]
    prep = [o for o in pads if "prep" in scopes.scope_names(o.scope)]
    assert sum(o.dur_ns for o in prep) >= 0.99 * sum(o.dur_ns for o in pads)


def test_chip_idle_spans_name_every_gap(chip):
    got, summary = scopes.reduce(chip), trace.reduce(chip)
    idle = dict(got["idle_spans"])
    assert sum(idle.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"], rel=1e-6)
    assert all(k.startswith(scopes.HOST_PREFIX) or k == "no host event"
               for k in idle)
    assert idle["repro.build"] > 0 and idle["repro.swap"] > 0
