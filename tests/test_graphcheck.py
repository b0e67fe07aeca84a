"""graphcheck — compiled-graph contract analyzer (docs/design.md #10).

Four layers under test:

* Per-rule positive/negative fixtures: synthetic in-memory GraphSpecs
  that violate exactly one contract (a materialised [n, n] block, a
  smuggled collective, a callback, a dropped donation, an unaudited
  narrowing cast, an over-budget temp) and their clean twins.
* The shipped-tree self-check: the full registry traces with ZERO
  findings and matches the committed golden fingerprints (trace-level
  rules; the big-shape GRC001 compiles run in the dedicated CI job and
  are spot-checked here through one cheap synthetic budget).
* Seeded regression: reverting ``engine.total_loss`` to the
  materialised [n, k] graph trips the analyzer.
* The CLI surface: flags, exit codes, golden drift diff.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.graph import budgets, fingerprint as fp, rules
from repro.analysis.graph.entrypoints import GraphSpec, N, by_name

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(REPO, "tests", "fixtures", "graphs.json")


def _spec(fn, args, *, name="test.synthetic", tags=("hot",), **over):
    kw = over.pop("kwargs", {})
    return GraphSpec(name=name, build=lambda: (fn, args, kw),
                     tags=frozenset(tags), **over)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _analyze_one(spec, **kw):
    report, prints = rules.analyze([spec], with_budgets=kw.pop(
        "with_budgets", False), **kw)
    return report, prints


# ---------------------------------------------------------------------------
# Per-rule positive/negative fixtures
# ---------------------------------------------------------------------------

def test_grc002_flags_materialised_nn_block():
    @jax.jit
    def materialised(x):
        dmat = jnp.sum((x[:, None, :] - x[None, :, :]) ** 2, axis=-1)
        return jnp.sum(jnp.min(dmat, axis=1))

    report, _ = _analyze_one(
        _spec(materialised, (_f32(N, 4),), tags=("hot", "streaming")))
    # one finding per distinct materialised intermediate (the broadcast
    # difference, its square, and the reduced [n, n] block)
    assert report.findings and \
        {f.rule for f in report.findings} == {"GRC002"}
    assert f"n={N}" in report.findings[0].message


def test_grc002_clean_on_streamed_form_and_untagged():
    @jax.jit
    def streamed(x):
        def body(acc, row):
            return acc + jnp.min(jnp.sum((x - row) ** 2, axis=1)), None
        out, _ = jax.lax.scan(body, jnp.float32(0.0), x)
        return out

    report, _ = _analyze_one(
        _spec(streamed, (_f32(N, 4),), tags=("hot", "streaming")))
    assert report.findings == []

    @jax.jit
    def materialised(x):
        return jnp.sum(x[:, None, :] - x[None, :, :])

    # the same block is legal without the streaming tag (e.g. predict,
    # where [rows, k] IS the product)
    report, _ = _analyze_one(_spec(materialised, (_f32(N, 4),)))
    assert report.findings == []


def _psum_fn():
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(jax.devices()[:1], ("i",))

    @jax.jit
    def f(x):
        return jax.shard_map(lambda a: jax.lax.psum(a, "i"), mesh=mesh,
                             in_specs=P("i"), out_specs=P())(x)
    return f


def test_grc003_flags_undeclared_collective():
    report, _ = _analyze_one(_spec(_psum_fn(), (_f32(8),)))
    got = sorted(f.rule for f in report.findings)
    assert got == ["GRC003", "GRC003"]          # psum AND shard_map
    assert any("psum count 1 != declared 0" in f.message
               for f in report.findings)


def test_grc003_clean_when_census_declared():
    report, _ = _analyze_one(
        _spec(_psum_fn(), (_f32(8),),
              collectives={"psum": 1, "shard_map": 1}))
    assert report.findings == []


def test_grc004_flags_callback_and_ignores_const_staging():
    import numpy as np

    @jax.jit
    def with_cb(x):
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct(x.shape, x.dtype),
            x)

    report, _ = _analyze_one(_spec(with_cb, (_f32(8),)))
    assert [f.rule for f in report.findings] == ["GRC004"]
    assert "pure_callback" in report.findings[0].message

    # jnp.asarray on a host table stages a constant via device_put —
    # constant placement, not a runtime round-trip
    table = np.arange(16, dtype=np.float32)

    @jax.jit
    def with_const(x):
        return x + jnp.asarray(table)

    report, _ = _analyze_one(_spec(with_const, (_f32(16),)))
    assert report.findings == []


def test_grc005_flags_dropped_donation():
    def f(x, y):
        return x + y, y

    undonated = jax.jit(f)
    donated = jax.jit(f, donate_argnums=(0,))
    args = (_f32(32), _f32(32))

    report, _ = _analyze_one(_spec(undonated, args, donated_leaves=1))
    assert [f_.rule for f_ in report.findings] == ["GRC005"]
    assert "0 aliased buffer(s)" in report.findings[0].message

    report, _ = _analyze_one(_spec(donated, args, donated_leaves=1))
    assert report.findings == []


def test_grc006_flags_unaudited_narrowing():
    @jax.jit
    def narrowing(x):
        return jnp.sum(x.astype(jnp.bfloat16).astype(jnp.float32))

    spec = _spec(narrowing, (_f32(64),))
    report, _ = _analyze_one(spec)
    assert [f.rule for f in report.findings] == ["GRC006"]
    assert "bfloat16" in report.findings[0].message

    # the widening f32->f64-free cast back up is never flagged, and an
    # audited allowance silences the finding
    report, _ = _analyze_one(
        _spec(narrowing, (_f32(64),), allowed_narrowing=1))
    assert report.findings == []


def test_grc001_budget_positive_negative(monkeypatch):
    n, k = 4096, 64
    monkeypatch.setitem(
        budgets._BUDGETS, "test.synthetic",
        (lambda s: s["n"] * s["k"] * 4 // 10, "n*k*4 // 10 (test)"))
    monkeypatch.setitem(budgets._SHAPES, "test.synthetic",
                        {"n": n, "k": k})

    def materialised(x, med):
        return jnp.sum(jnp.min(
            jnp.sum((x[:, None, :] - med[None, :, :]) ** 2, axis=-1),
            axis=1))

    def streamed(x, med):
        def body(acc, row):
            return acc + jnp.min(jnp.sum((med - row) ** 2, axis=1)), None
        out, _ = jax.lax.scan(body, jnp.float32(0.0), x)
        return out

    args = (_f32(n, 4), _f32(k, 4))
    for fn, expect in ((materialised, ["GRC001"]), (streamed, [])):
        spec = _spec(jax.jit(fn), args, budget="test.synthetic")
        spec = dataclasses.replace(spec, build_big=spec.build)
        report, _ = _analyze_one(spec, with_budgets=True)
        assert [f.rule for f in report.findings] == expect, \
            [f.message for f in report.findings]


def test_grc000_drift_positive_negative():
    @jax.jit
    def f(x):
        return jnp.sum(x * x)

    spec = _spec(f, (_f32(16),))
    _, prints = _analyze_one(spec)
    golden = fp.merge_golden(None, prints)

    # clean against its own fingerprint
    report, _ = _analyze_one(spec, golden_doc=golden)
    assert report.findings == []

    # perturb: census drift is reported primitive-by-primitive
    bad = json.loads(json.dumps(golden))
    entry = bad["goldens"][jax.__version__]["test.synthetic"]
    entry["hash"] = "0" * 16
    entry["census"]["dot_general"] = 7
    report, _ = _analyze_one(spec, golden_doc=bad)
    assert [f.rule for f in report.findings] == ["GRC000"]
    assert "dot_general: 7 -> 0 (-7)" in report.findings[0].message

    # a golden for a DIFFERENT jax version is a note, not a finding
    other = {"tool": "graphcheck", "version": 1,
             "goldens": {"0.0.0": {}}}
    report, _ = _analyze_one(spec, golden_doc=other)
    assert report.findings == []
    assert any("no goldens committed" in n for n in report.notes)


# ---------------------------------------------------------------------------
# Shipped-tree self-check + seeded regression
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def shipped_report():
    golden = fp.load_golden(GOLDEN) if os.path.isfile(GOLDEN) else None
    return rules.analyze(golden_doc=golden, with_budgets=False)


def test_shipped_tree_is_clean(shipped_report):
    report, _ = shipped_report
    assert report.findings == [], rules.format_human(report)


def test_shipped_tree_matches_committed_golden(shipped_report):
    assert os.path.isfile(GOLDEN), \
        "tests/fixtures/graphs.json missing — REGEN_GOLDEN=1 python -m " \
        "repro.analysis.graph"
    golden = fp.load_golden(GOLDEN)
    vgold = fp.golden_for_version(golden)
    if vgold is None:
        pytest.skip(f"no goldens for jax {jax.__version__}")
    _, prints = shipped_report
    assert sorted(prints) == sorted(vgold)


def test_registry_covers_known_hot_drivers(shipped_report):
    report, _ = shipped_report
    names = set(report.entrypoints)
    for required in ("core._build_fused[pic]", "core._swap_iter[pic]",
                     "core._build_batch[pic]", "core._swap_batch[pic]",
                     "engine.total_loss", "engine.medoid_cache",
                     "kernels.stream_build_g_stats", "kernels.stream_top2",
                     "api.get_predict_fn", "api.get_assign_fn",
                     "dist.build_phase[pic]", "dist.swap_iter[pic]"):
        assert required in names, f"{required} fell out of the registry"


def test_seeded_regression_materialised_total_loss():
    """A revert of engine.total_loss to the pre-streaming materialised
    [n, k] graph must trip the analyzer (GRC002 at trace level)."""
    from repro.core.distances import get_metric

    @jax.jit
    def reverted(data, medoids):
        dmat = get_metric("l2")(data, data[medoids])
        return jnp.sum(jnp.min(dmat, axis=1))

    real = by_name()["engine.total_loss"]
    seeded = GraphSpec(
        name=real.name, build=lambda: (reverted, (_f32(N, 8),
                                                  jax.ShapeDtypeStruct(
                                                      (N,), jnp.int32)), {}),
        tags=real.tags, n=real.n)
    report, _ = _analyze_one(seeded)
    assert "GRC002" in [f.rule for f in report.findings]


def test_budget_formulas_scale_with_shape():
    base = budgets.budget_bytes("engine.total_loss")
    assert budgets.budget_bytes("engine.total_loss",
                                n=2 * budgets.N_BIG) == 2 * base
    assert "n*k*4" in budgets.budget_doc("engine.total_loss")
    for name in budgets.budget_names():
        assert budgets.budget_bytes(name) > 0
        assert budgets.shape_for(name)


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def _cli(*argv, env_extra=None):
    env = {"PYTHONPATH": os.path.join(REPO, "src"),
           "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", "/root")}
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.graph", *argv],
        capture_output=True, text=True, cwd=REPO, env=env)


def test_cli_list_rules_and_entrypoints():
    r = _cli("--list-rules")
    assert r.returncode == 0
    for rid in rules.ALL_RULES:
        assert rid in r.stdout
    r = _cli("--list-entrypoints")
    assert r.returncode == 0
    assert "engine.total_loss" in r.stdout
    assert "core._swap_iter[pic]" in r.stdout


def test_cli_unknown_rule_and_entrypoint_exit_2():
    assert _cli("--rules", "GRC999").returncode == 2
    assert _cli("--entrypoints", "no.such").returncode == 2


def test_cli_single_entrypoint_json_clean():
    r = _cli("--entrypoints", "engine.total_loss", "--skip-budgets",
             "--format", "json")
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["tool"] == "graphcheck"
    assert doc["findings"] == []
    assert doc["entrypoints"] == ["engine.total_loss"]
    assert "engine.total_loss" in doc["fingerprints"]


def test_cli_golden_diff_detects_drift(tmp_path):
    golden = fp.load_golden(GOLDEN)
    vgold = fp.golden_for_version(golden)
    if vgold is None:
        pytest.skip(f"no goldens for jax {jax.__version__}")
    bad = json.loads(json.dumps(golden))
    entry = bad["goldens"][jax.__version__]["engine.total_loss"]
    entry["hash"] = "0" * 16
    entry["census"]["dot_general"] = entry["census"].get(
        "dot_general", 0) + 2
    bad_path = tmp_path / "graphs_bad.json"
    bad_path.write_text(json.dumps(bad))
    r = _cli("--entrypoints", "engine.total_loss", "--skip-budgets",
             "--golden", str(bad_path), "--golden-diff")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "dot_general" in r.stdout and "-2" in r.stdout
    # the committed golden itself diffs clean
    r = _cli("--entrypoints", "engine.total_loss", "--skip-budgets",
             "--golden-diff")
    assert r.returncode == 0, r.stdout + r.stderr
