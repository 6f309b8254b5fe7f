"""Pipeline parallelism (GPipe over a stage axis): subprocess multi-device
test — forward equals sequential composition; gradients flow."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = [pytest.mark.distributed, pytest.mark.slow]

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_DRIVER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.distributed.pipeline import pipeline_apply, bubble_fraction

    # Auto axes: the schedule is written for shard_map under an implicitly
    # sharded mesh, and jax.make_mesh defaults to Explicit axes
    mesh = jax.make_mesh((4, 2), ("pod", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    S, D = 4, 16
    rng = np.random.default_rng(0)
    Ws = jnp.asarray(rng.normal(0, 0.5, (S, D, D)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (8, D)), jnp.float32)

    def stage_fn(W, xb):
        return jnp.tanh(xb @ W)

    fn = pipeline_apply(stage_fn, mesh, stage_axis="pod", n_micro=4,
                        data_axes=("data",))
    y = fn(Ws, x)

    yref = x
    for s in range(S):
        yref = jnp.tanh(yref @ Ws[s])

    report = {
        "fwd_close": bool(np.allclose(np.asarray(y), np.asarray(yref),
                                      atol=1e-5)),
        "bubble": bubble_fraction(4, 4),
    }

    def loss(Ws):
        return jnp.sum(fn(Ws, x) ** 2)

    def loss_ref(Ws):
        yy = x
        for s in range(S):
            yy = jnp.tanh(yy @ Ws[s])
        return jnp.sum(yy ** 2)

    g = jax.grad(loss)(Ws)
    gref = jax.grad(loss_ref)(Ws)
    report["grad_close"] = bool(np.allclose(np.asarray(g),
                                            np.asarray(gref), atol=1e-4))
    print("JSON" + json.dumps(report))
""")


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _DRIVER], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("JSON")][-1]
    return json.loads(line[4:])


def test_pipeline_forward_matches_sequential(report):
    assert report["fwd_close"]


def test_pipeline_gradients_flow(report):
    assert report["grad_close"]


def test_bubble_fraction(report):
    assert report["bubble"] == pytest.approx(3 / 7)
