"""Elastic restore: a checkpoint saved under one mesh restores onto a
DIFFERENT mesh (data axis shrunk after a simulated host loss) and training
continues bit-exactly — the checkpoint stores global arrays, restore
re-shards via device_put (subprocess: 8 host devices)."""
import json
import subprocess
import sys
import textwrap

import jax
import pytest

_SUBPROC = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.runtime import checkpoint as ckpt
    from repro.runtime.elastic import build_mesh, plan_remesh

    # "before failure": 8 chips, mesh (4 data, 2 model)
    mesh8 = jax.make_mesh((4, 2), ("data", "model"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh8 = NamedSharding(mesh8, P("data", "model"))
    w = jax.device_put(jnp.arange(32.0).reshape(8, 4), sh8)
    state = {"w": w, "step": jnp.int32(7)}
    d = tempfile.mkdtemp()
    ckpt.save(d, 7, state, extra={"note": "pre-failure"})

    # "after failure": 2 hosts lost -> plan a 4-chip mesh, same model extent
    plan = plan_remesh(4, model_parallel=2)
    mesh4 = build_mesh(plan)
    sh4 = NamedSharding(mesh4, P("data", "model"))
    restored, meta = ckpt.restore(d, state, shardings={"w": sh4, "step": None})
    ok_val = bool((np.asarray(restored["w"]) == np.asarray(w)).all())
    ok_shard = restored["w"].sharding.mesh.shape == dict(data=2, model=2)
    print(json.dumps({"plan": list(plan.shape), "ok_val": ok_val,
                      "ok_shard": ok_shard, "step": int(meta["step"])}))
""")


def test_restore_onto_smaller_mesh():
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["plan"] == [2, 2]
    assert res["ok_val"] and res["ok_shard"] and res["step"] == 7, res
