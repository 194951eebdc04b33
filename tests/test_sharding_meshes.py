"""Sharding rule table + mesh tests on forced host devices.

Runs in a subprocess (XLA device count locks at first jax init), asserting:
rule resolution, divisibility fallbacks, param spec positional rules, and a
real sharded train step on a smoke mesh with checkpoint->remesh restore
(the elastic path with actual device movement).
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.sharding import partition as P


# --------------------------------------------------------------------------
# pure rule-table tests (no mesh needed)
# --------------------------------------------------------------------------

def test_rules_drop_without_mesh():
    P.configure(None)
    assert P.resolve_axes((8, 16), ("batch", "seq")) == \
        __import__("jax").sharding.PartitionSpec(None, None)


def test_rules_overridden_context():
    P.configure(None)
    base = P.current_rules()
    with P.rules_overridden({"seq": None}):
        assert P.current_rules()["seq"] is None
    assert P.current_rules() == base


# --------------------------------------------------------------------------
# subprocess: real 8-device mesh
# --------------------------------------------------------------------------

_SUB = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro import configs
    from repro.launch import mesh as mesh_lib
    from repro.sharding import (configure, make_param_shardings,
                                named_sharding, resolve_axes)
    from repro.optim import AdamWConfig
    from repro.train import (Checkpointer, init_train_state,
                             make_train_step, state_shardings,
                             batch_shardings)
    import tempfile

    out = {}
    mesh = mesh_lib.make_smoke_mesh()            # (data=2, model=4)
    configure(mesh)

    # 1. divisibility fallback: dim not divisible by axis -> replicated
    spec = resolve_axes((6, 16), ("batch", "seq"))   # batch 6 % 2 == 0
    out["spec_ok"] = str(spec)
    spec2 = resolve_axes((5, 16), ("batch", "seq"))  # 5 % 2 -> drop
    out["spec_fallback"] = str(spec2)

    # 2. sharded end-to-end train step + elastic re-mesh restore
    cfg = configs.reduced_config("deepseek-7b")
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    shapes = jax.eval_shape(lambda: state)
    st_sh = state_shardings(shapes, mesh)
    step = jax.jit(make_train_step(cfg, AdamWConfig(warmup_steps=1)),
                   in_shardings=(st_sh, None), out_shardings=(st_sh, None),
                   donate_argnums=(0,))
    batch = {"tokens": jnp.zeros((4, 32), jnp.int32),
             "labels": jnp.zeros((4, 32), jnp.int32)}
    with mesh:
        state = jax.device_put(state, st_sh)
        state, m = step(state, batch)
        state, m = step(state, batch)
    out["loss"] = float(m["loss"])
    out["sharded"] = str(
        jax.tree_util.tree_leaves(state.params)[1].sharding)

    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(2, state, extra={"next_step": 2})

        # elastic: restore onto a *different* mesh shape (4, 2)
        from repro.launch.mesh import auto_mesh
        mesh2 = auto_mesh((4, 2), ("data", "model"))
        configure(mesh2)
        st_sh2 = state_shardings(shapes, mesh2)
        state2, extra = ck.restore(shapes, shardings=st_sh2)
        step2 = jax.jit(make_train_step(cfg, AdamWConfig(warmup_steps=1)),
                        in_shardings=(st_sh2, None),
                        out_shardings=(st_sh2, None), donate_argnums=(0,))
        with mesh2:
            state2, m2 = step2(state2, batch)
    out["loss_after_remesh"] = float(m2["loss"])
    out["resumed_step"] = int(extra["next_step"])
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def subproc_result():
    res = subprocess.run(
        [sys.executable, "-c", _SUB], capture_output=True, text=True,
        env={**__import__("os").environ,
             "PYTHONPATH": str(Path(__file__).resolve().parents[1] / "src")},
        timeout=540)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, res.stdout
    return json.loads(line[-1][len("RESULT "):])


def test_divisibility_fallback(subproc_result):
    assert "'data'" in subproc_result["spec_ok"].replace('"', "'")
    # batch=5 not divisible by data=2 -> replicated
    assert subproc_result["spec_fallback"].count("data") == 0


def test_sharded_train_step_runs(subproc_result):
    import math
    assert math.isfinite(subproc_result["loss"])


def test_params_actually_sharded(subproc_result):
    assert "NamedSharding" in subproc_result["sharded"]


def test_elastic_remesh_restore(subproc_result):
    import math
    assert subproc_result["resumed_step"] == 2
    assert math.isfinite(subproc_result["loss_after_remesh"])
