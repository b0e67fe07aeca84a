"""End-to-end compressed-gradient training on a (pod, data) mesh:
loss must track the uncompressed step closely (error feedback), and the
HLO must actually carry int8 on the pod axis (subprocess: 8 devices)."""
import json
import subprocess
import sys
import textwrap

import jax
import pytest

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, json
    from repro.configs import get_reduced
    from repro.models import model as M
    from repro.train import OptConfig, init_opt_state, make_train_step, synthetic_batch
    from repro.train.compressed import init_pod_residuals, make_compressed_train_step

    mesh = jax.make_mesh((2, 4), ("pod", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_reduced("qwen3_1_7b")
    ocfg = OptConfig(lr=5e-3, warmup_steps=2)

    def run(compressed: bool, steps=8):
        params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        opt = init_opt_state(params, ocfg)
        losses = []
        if compressed:
            res = init_pod_residuals(params, 2)
            step = jax.jit(make_compressed_train_step(cfg, ocfg, mesh))
            for i in range(steps):
                b = synthetic_batch(cfg, 8, 32, i)
                params, opt, res, m = step(params, opt, res, b)
                losses.append(float(m["loss"]))
        else:
            step = jax.jit(make_train_step(cfg, ocfg, 1))
            for i in range(steps):
                b = synthetic_batch(cfg, 8, 32, i)
                params, opt, m = step(params, opt, b)
                losses.append(float(m["loss"]))
        return losses

    base = run(False)
    comp = run(True)
    # int8 actually on the wire?
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    opt = init_opt_state(params, ocfg)
    res = init_pod_residuals(params, 2)
    step = make_compressed_train_step(cfg, ocfg, mesh)
    txt = jax.jit(step).lower(params, opt, res,
                              synthetic_batch(cfg, 8, 32, 0)).compile().as_text()
    int8_wire = ("s8[" in txt) and ("all-gather" in txt or "all-reduce" in txt)
    print(json.dumps({"base": base, "comp": comp, "int8_wire": bool(int8_wire)}))
""")


def test_compressed_training_tracks_exact():
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["int8_wire"], "no int8 collective found in HLO"
    base, comp = res["base"], res["comp"]
    assert comp[-1] < comp[0], "compressed training must converge"
    # error feedback: final losses within a few percent of exact
    assert abs(comp[-1] - base[-1]) / base[-1] < 0.05, (base, comp)
