"""Distribution substrate: int8 EF compression math, sharding rules, and a
subprocess multi-device check (shard_map compressed psum vs exact psum;
distributed BanditPAM equivalence lives in test_distributed_banditpam)."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.compression import dequantize_int8, quantize_int8

def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32))
    q, s = quantize_int8(x)
    err = jnp.max(jnp.abs(dequantize_int8(q, s) - x))
    assert float(err) <= float(s) * 0.5 + 1e-6


def test_error_feedback_reduces_bias():
    """Over repeated steps the EF residual keeps the *accumulated* quantized
    sum close to the accumulated true sum (bias does not grow)."""
    rng = np.random.default_rng(1)
    residual = jnp.zeros((32,), jnp.float32)
    acc_true = np.zeros(32)
    acc_q = np.zeros(32)
    for t in range(50):
        g = jnp.asarray(rng.standard_normal(32).astype(np.float32)) * 0.01
        xr = g + residual
        q, s = quantize_int8(xr)
        deq = dequantize_int8(q, s)
        residual = xr - deq
        acc_true += np.asarray(g)
        acc_q += np.asarray(deq)
    # EF guarantees |acc_true - acc_q| = |last residual| <= one quantum
    assert np.max(np.abs(acc_true - acc_q)) <= float(s) + 1e-6


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import psum_int8_ef

    mesh = jax.make_mesh((2, 4), ("pod", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    x = jnp.arange(2 * 4 * 16, dtype=jnp.float32).reshape(8, 16) * 0.01
    res = jnp.zeros((8, 16), jnp.float32)

    def f(xl, rl):
        s, r = psum_int8_ef(xl[0], rl[0], "pod")
        exact = jax.lax.psum(xl[0], "pod")
        return s[None], exact[None], r[None]

    g = jax.shard_map(f, mesh=mesh, in_specs=(P(("pod", "data")), P(("pod", "data"))),
                      out_specs=(P(("pod", "data")), P(("pod", "data")),
                                 P(("pod", "data"))))
    s, exact, r = g(x.reshape(8, 16), res)
    err = float(jnp.max(jnp.abs(s - exact)))
    scale = float(jnp.max(jnp.abs(exact)))
    print(json.dumps({"err": err, "scale": scale}))
""")


def test_compressed_psum_matches_exact_subprocess():
    out = subprocess.run([sys.executable, "-c", _SUBPROC], capture_output=True,
                         text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                                          "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # int8 quantization: relative error ~< 1/127 per term
    assert res["err"] <= res["scale"] / 64 + 1e-5, res


def test_sharding_rules_noop_without_mesh():
    from repro.distributed.sharding import shard, spec_for
    x = jnp.ones((4, 4))
    assert shard(x, "batch", "d_model") is x
    assert spec_for("batch") == jax.sharding.PartitionSpec()


def test_spec_for_with_mesh_rules():
    from repro.distributed import sharding as sh
    # fake mesh context: use the 1-device mesh but full rule table
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh.set_mesh(mesh)
    try:
        assert sh.spec_for("batch", None, "ff") == \
            jax.sharding.PartitionSpec(None, None, "model")
    finally:
        sh.clear()
