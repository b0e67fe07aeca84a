"""tracecheck tests: rule corpus, suppressions, reports, CLI, import
graph, and the runtime transfer-guard/dispatch fixtures.

The static half runs on the fixture corpus under
``tests/fixtures/tracecheck`` (``bad/`` known violations, ``clean/``
known-conformant counterparts) plus a self-check over the shipped
``src/repro`` tree; the runtime half drives full ``BanditPAM.fit`` under
``jax.transfer_guard("disallow")`` and asserts the one-dispatch-per-
phase ledger in-test.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import config as cfg_mod
from repro.analysis import engine, imports
from repro.analysis.guard import expected_dispatches

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src" / "repro"
CORPUS = Path(__file__).parent / "fixtures" / "tracecheck"

ALL_RULES = ("TRC000", "TRC001", "TRC002", "TRC003", "TRC004", "TRC005")


def _run(path):
    return engine.run([str(path)], cfg_mod.default_config())


def _rules_hit(report):
    return set(report.counts)


# ---------------------------------------------------------------- static

def test_bad_corpus_fires_every_rule():
    report = _run(CORPUS / "bad")
    assert _rules_hit(report) == set(ALL_RULES)
    assert len(report.findings) >= len(ALL_RULES)


@pytest.mark.parametrize("rule,path_suffix", [
    ("TRC001", "bad/core/hot_loop.py"),
    ("TRC002", "bad/core/hot_loop.py"),
    ("TRC003", "bad/core/rng.py"),
    ("TRC004", "bad/core/stats_backend.py"),
    ("TRC005", "bad/core/banditpam.py"),
    ("TRC005", "bad/kernels/stream.py"),
    ("TRC005", "bad/serve/drift.py"),
    ("TRC005", "bad/runtime/checkpoint.py"),
    ("TRC000", "bad/core/suppressed.py"),
])
def test_rule_positive_location(rule, path_suffix):
    report = _run(CORPUS / "bad")
    hits = [f for f in report.findings
            if f.rule == rule and f.path.endswith(path_suffix)]
    assert hits, f"{rule} did not fire in {path_suffix}"
    assert all(f.line > 0 for f in hits)


def test_clean_corpus_has_no_findings():
    report = _run(CORPUS / "clean")
    assert report.findings == []
    # ...and the justified suppression in clean/core/engine.py counted.
    assert report.suppressed >= 1


def test_host_orchestration_is_not_flagged():
    # hot_loop.host_driver syncs and loops freely — not jit-reachable.
    report = _run(CORPUS / "bad" / "core" / "hot_loop.py")
    assert not any(f.function == "host_driver" for f in report.findings)


def test_bare_suppression_suppresses_but_raises_trc000():
    report = _run(CORPUS / "bad" / "core" / "suppressed.py")
    assert [f.rule for f in report.findings] == ["TRC000"]
    assert report.suppressed == 1


def test_justified_suppression_is_silent():
    report = _run(CORPUS / "clean" / "core" / "engine.py")
    assert report.findings == []
    assert report.suppressed == 1


def test_shipped_tree_is_clean_under_shipped_config():
    report = _run(SRC)
    assert report.findings == [], "\n" + engine.format_human(report)
    # The tree's suppressions all carry justifications (else TRC000
    # findings would have failed the assert above) and are in use.
    assert report.suppressed > 0


def test_json_report_schema():
    report = _run(CORPUS / "bad")
    doc = engine.report_to_json(report)
    assert doc["tool"] == "tracecheck" and doc["version"] == 1
    assert doc["files_scanned"] == report.files_scanned
    assert sum(doc["counts"].values()) == len(doc["findings"])
    for f in doc["findings"]:
        assert set(f) == {"rule", "path", "line", "col", "message",
                          "function"}
        assert f["rule"] in ALL_RULES
    json.dumps(doc)  # serializable


# ------------------------------------------------------------------ CLI

def _cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=cwd,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"},
    )


def test_cli_nonzero_on_violations_and_json(tmp_path):
    out = tmp_path / "report.json"
    proc = _cli(str(CORPUS / "bad"), "--format", "json",
                "--output", str(out))
    assert proc.returncode == 1, proc.stderr
    doc = json.loads(proc.stdout)
    assert set(doc["counts"]) == set(ALL_RULES)
    assert json.loads(out.read_text()) == doc


def test_cli_zero_on_shipped_tree():
    proc = _cli("src/repro")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_cli_rule_filter_and_list():
    proc = _cli(str(CORPUS / "bad"), "--rules", "TRC004")
    assert proc.returncode == 1
    assert "TRC004" in proc.stdout and "TRC001" not in proc.stdout
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rid in ALL_RULES:
        assert rid in proc.stdout


# --------------------------------------------------------- import graph

def test_import_graph_classification():
    cfg = cfg_mod.default_config()
    report = imports.build_report(str(REPO), cfg)
    assert report["repro.api.estimator"]["status"] == "live"
    assert report["repro.core.banditpam"]["status"] == "live"
    assert report["repro.runtime.checkpoint"]["status"] == "live"
    # LM scaffolding is dormant and quarantined.
    for mod in ("repro.models.model", "repro.train.train_step",
                "repro.serve.lm", "repro.runtime.fault"):
        assert report[mod]["status"] != "live", mod
        assert mod in cfg.quarantine


def test_quarantine_contract_holds():
    cfg = cfg_mod.default_config()
    report = imports.build_report(str(REPO), cfg)
    undocumented, stale = imports.check_quarantine(report, cfg)
    assert undocumented == [], f"undocumented dormant modules: {undocumented}"
    assert stale == [], f"stale quarantine entries: {stale}"


# -------------------------------------------------------- runtime guard

@pytest.fixture(scope="module")
def blobs():
    rng = np.random.default_rng(3)
    return np.concatenate(
        [rng.normal(loc=c, size=(60, 5)) for c in (0.0, 5.0, 10.0)]
    ).astype(np.float32)


@pytest.mark.parametrize("reuse", ["pic", "none"])
def test_transfer_guard_full_fit(fit_guard, blobs, reuse):
    from repro.core.banditpam import BanditPAM
    report = fit_guard.fit(BanditPAM(3, seed=0, reuse=reuse), blobs)
    # The in-test dispatch contract: one fused BUILD dispatch, one
    # dispatch per SWAP iteration (n_swaps accepts + converging reject);
    # under reuse="none" one exact certify step per rejected bandit pick.
    iters = report.n_swaps + (1 if report.converged else 0)
    want = {"build": 1, "swap": iters}
    if reuse == "none":
        want["certify"] = report.certify_swaps + 1
    assert report.dispatches_by_phase == want


def test_transfer_guard_warm_start(fit_guard, blobs):
    from repro.core.banditpam import BanditPAM
    est = BanditPAM(3, seed=0, reuse="pic")
    cold = est.fit(blobs)
    report = fit_guard.fit(est, blobs, warm_start=cold.medoids)
    assert report.dispatches_by_phase == expected_dispatches(
        report, warm=True)
    assert "build" not in report.dispatches_by_phase
    assert report.medoids.tolist() == cold.medoids.tolist()


def test_transfer_guard_fit_batch(fit_guard, blobs):
    """The batched multi-fit path under transfer_guard("disallow"):
    staging is spanned by host_stage, ledgers leave in ONE host_read,
    and the whole batch costs {"build": 1, "swap": 1} dispatches."""
    from repro.core.banditpam import BanditPAM
    est = BanditPAM(3, seed=0, reuse="pic")
    # ragged lane sizes exercise the padded staging path
    datasets = [blobs, blobs[:150], blobs[:97]]
    batch = fit_guard.fit_batch(est, datasets, seeds=[0, 1, 2])
    assert batch.dispatches_by_phase == {"build": 1, "swap": 1}
    # per-fit parity with the single-fit path still holds guarded:
    # medoids to the bit; the final loss reduction on a ragged (padded)
    # lane is allowed a last-bit difference (test_multifit contract)
    solo = BanditPAM(3, seed=1, reuse="pic").fit(blobs[:150])
    assert batch[1].medoids.tolist() == solo.medoids.tolist()
    np.testing.assert_allclose(batch[1].loss, solo.loss, rtol=1e-5)


def test_trace_guard_actually_guards(trace_guard):
    import jax.numpy as jnp
    x = jnp.arange(4)
    with trace_guard():
        with pytest.raises(Exception, match="[Dd]isallow"):
            x * 2  # implicit host→device upload of the Python scalar


def test_host_read_is_sanctioned(trace_guard):
    import jax.numpy as jnp
    from repro.core.engine import host_read, host_stage
    with trace_guard():
        with host_stage("test staging"):
            x = jnp.asarray(np.arange(4.0, dtype=np.float32))
        y = x + x
        out = host_read((y, y.sum()))
    assert out[0].tolist() == [0.0, 2.0, 4.0, 6.0]
    with pytest.raises(ValueError):
        with host_stage(""):
            pass
