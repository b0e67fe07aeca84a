"""A cell on a new data set, or with a new job, is added by new files and
new entries of ``BENCHMARK.json`` alone.

In a copy of the benchmark, each test adds a configuration, a workload
file and, for a new job, the job's file, appends the entries, and runs
the cell's rehearsal: the harness has to find every part by its name and
drive the cell through to its checks, with no file that the copy had
before changed.  The cell on the scRNA twin is the 10x Genomics PBMC
deployment of the BanditPAM paper (sec. 5: l1, d = 1000, k = 5).
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from bench import harness

SOURCE = "https://arxiv.org/abs/2006.06856"
SCRNA = {
    "source": SOURCE,
    "deployment": "BanditPAM (Tiwari et al., NeurIPS 2020), sec. 5: "
                  "k-medoids of the 10x Genomics PBMC 68k scRNA-seq "
                  "counts, 68,579 cells of 1,000 genes, l1 distance, k = 5",
    "reduced": ["n"],
    "dataset": "scrna_like",
    "data_seed": 0,
    "n": 20000,
    "d": 1000,
    "metric": "l1",
    "k": 5,
    "solver": "banditpam",
    "dtype": "float32",
    "matmul_precision": "highest",
    "guarantees": "medoids at the exact-PAM tier (a swap optimum); "
                  "reported loss and labels those of float32 distances, "
                  "against explicit differences",
    "reference": "bench/reference.py",
    "assumed": {
        "dataset": "no network: the statistical twin scrna_like "
                   "(bench/datasets/scrna_like.py) stands for the PBMC "
                   "counts at their width and sparsity",
        "data_seed": "one fixed data set, as a deployment has",
        "n": "68,579 cut to 20,000: the reference's exact SWAP step "
             "grows as n^2 d",
        "solver": "banditpam, the paper's algorithm",
    },
}
FIT = {"traffic": {"job": "fit", "solver_seeds": [0, 1]},
       "limits": {"loss_gap": 1e-6, "label_gap": 1e-4, "swap_gain": 1e-5}}

# A job of its own file: it sums its data set, and checks the sum.
PROBE_JOB = '''
import numpy as np

from bench import datagen


class Job:
    def __init__(self, config, traffic, seed):
        self.config = config
        self.attempted = 0

    def setup(self):
        self.x = datagen.dataset(self.config, int(self.config["n"]))

    def window(self, seconds):
        self.attempted += 1
        self.total = float(self.x.sum(dtype=np.float64))
        return {}

    def traced(self):
        pass

    def release(self):
        pass

    def check(self):
        return {"sum_gap": abs(self.total - float(np.sum(
            self.x.astype(np.float64))))}

    def layer_context(self):
        return {}
'''
PROBE = {"traffic": {"job": "probe_sum"}, "limits": {"sum_gap": 1e-6}}


def copy_benchmark(dst) -> None:
    """``BENCHMARK.json`` and the benchmark's files, with the program's
    sources linked beside them."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(harness.HERE, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(harness.ROOT, "src"), os.path.join(dst, "src"))


def add_cell(root, config_name: str, config: dict, traffic: str,
             spec: dict, why: str, files=None) -> str:
    """Add the configuration ``config_name``, its cell of ``traffic``
    and any further ``files`` (paths under ``bench/``) to the benchmark
    at ``root``, by new files and new entries of ``BENCHMARK.json`` only;
    returns the cell's name.  The cell joins every metric whose
    ``workloads`` hold a cell of the same traffic."""
    cell = f"{config_name}.{traffic}"
    news = dict(files or {})
    news[f"configs/{config_name}.json"] = json.dumps(config, indent=2)
    news[f"workloads/{cell}.json"] = json.dumps(spec, indent=2)
    for rel, text in news.items():
        path = os.path.join(root, "bench", rel)
        assert not os.path.exists(path), rel
        with open(path, "w") as f:
            f.write(text + "\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    same = {w["name"] for w in bench["workloads"] if w["traffic"] == traffic}
    bench["configs"].append({
        "name": config_name, "source": config["source"],
        "file": f"bench/configs/{config_name}.json",
        "reduced": config["reduced"], "why": why})
    bench["workloads"].append({"name": cell, "config": config_name,
                               "traffic": traffic, "chips": 1, "why": why})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if same & set(m.get("workloads", [])):
            m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=2)
        f.write("\n")
    return cell


def _digests(root) -> dict:
    out = {}
    for folder, dirs, files in os.walk(os.path.join(root, "bench")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _rehearse(root, cell: str, trace: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(2**35 + 5), "--seconds", "0.5", "--trace", trace,
         "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


def _runs_to_its_checks(p, checks) -> None:
    assert p.returncode == 1, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert "rehearsal, not a result" in p.stderr, p.stderr[-3000:]
    for name in checks:
        assert f"check {name} " in p.stderr, p.stderr[-3000:]


def test_new_data_set_by_files_alone(tmp_path):
    copy_benchmark(tmp_path)
    before = _digests(tmp_path)
    cell = add_cell(tmp_path, "scrna-l1", SCRNA, "fit", FIT,
                    "the paper's scRNA deployment: sparse l1 points")
    p = _rehearse(tmp_path, cell, "1")
    _runs_to_its_checks(p, FIT["limits"])
    after = _digests(tmp_path)
    assert {k: after[k] for k in before} == before


def test_new_job_by_files_alone(tmp_path):
    copy_benchmark(tmp_path)
    before = _digests(tmp_path)
    cell = add_cell(tmp_path, "scrna-l1", SCRNA, "probe_sum", PROBE,
                    "a job of its own file",
                    files={"probe_sum.py": PROBE_JOB})
    p = _rehearse(tmp_path, cell, "0")
    _runs_to_its_checks(p, PROBE["limits"])
    assert "check sum_gap 0.0 limit 1e-06" in p.stderr
    after = _digests(tmp_path)
    assert {k: after[k] for k in before} == before
