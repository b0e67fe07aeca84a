"""Pipeline parallelism: GPipe schedule over a 2-stage pod axis must equal
the single-device sequential forward (subprocess: 8 host devices)."""
import json
import subprocess
import sys
import textwrap

import jax
import pytest

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import PartitionSpec as P
    from repro.distributed.pipeline import pipeline_map

    mesh = jax.make_mesh((2, 4), ("pod", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    D, LAYERS, M, MB = 16, 4, 3, 8
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (LAYERS, D, D)) * 0.3

    def layer(w, x):
        return jnp.tanh(x @ w)

    def stage_fn(wstack, x):            # wstack [LAYERS/2, D, D]
        def body(h, w):
            return layer(w, h), None
        h, _ = jax.lax.scan(body, x, wstack)
        return h

    mbs = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))

    # reference: all layers sequentially
    ref = mbs
    for i in range(LAYERS):
        ref = jax.vmap(lambda x: layer(ws[i], x))(ref)

    run = pipeline_map(stage_fn, mesh, n_stages=2, axis="pod",
                       params_spec=P("pod"), x_spec=P(None))
    out = run(ws.reshape(2, LAYERS // 2, D, D).reshape(LAYERS, D, D), mbs)
    err = float(jnp.max(jnp.abs(out - ref)))

    # and gradients flow through the schedule
    def loss(w):
        return jnp.sum(run(w, mbs) ** 2)
    g = jax.grad(loss)(ws)
    gfinite = bool(jnp.isfinite(g).all())
    print(json.dumps({"err": err, "gfinite": gfinite}))
""")


def test_gpipe_matches_sequential():
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["err"] < 1e-5, res
    assert res["gfinite"], res
