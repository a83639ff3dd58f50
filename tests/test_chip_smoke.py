"""chip_smoke.py off the chip: it must refuse at once, name what it found
and print no result. What it does ON the chip is the script's own job."""

import os
import subprocess
import sys
import time
from pathlib import Path

SMOKE = Path(__file__).resolve().parent.parent / "chip_smoke.py"
sys.path.insert(0, str(SMOKE.parent))

import chip_smoke  # noqa: E402  (imports numpy only; JAX is touched in main)


def test_refuses_the_cpu_backend_in_seconds():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.perf_counter()
    r = subprocess.run(
        [sys.executable, str(SMOKE)], env=env, capture_output=True,
        text=True, timeout=120, cwd=str(SMOKE.parent),
    )
    assert r.returncode != 0
    assert r.stdout == ""
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr
    assert time.perf_counter() - t0 < 60


def test_numpy_ftrl_is_the_store_push():
    """The reference the server phase compares against agrees with
    kv.store.push/pull on the CPU."""
    import jax.numpy as jnp
    import numpy as np

    from parameter_server_tpu.kv import store
    from parameter_server_tpu.kv.updaters import Ftrl

    rng = np.random.default_rng(0)
    pushes = [
        (
            np.unique(rng.integers(1, 256, 64)),
            (3.0 * rng.normal(size=64)).astype(np.float32),
        )
        for _ in range(3)
    ]
    pushes = [(k, g[: len(k)]) for k, g in pushes]
    up = Ftrl(
        alpha=chip_smoke.ALPHA, beta=chip_smoke.BETA,
        lambda_l1=chip_smoke.L1, lambda_l2=chip_smoke.L2,
    )
    state = up.init(256, 1)
    per_push, weights = chip_smoke.numpy_ftrl(256, pushes)
    for (keys, g), want in zip(pushes, per_push):
        state = store.push(up, state, jnp.asarray(keys), jnp.asarray(g)[:, None])
        got = np.asarray(store.pull(up, state, jnp.asarray(keys))).ravel()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.count_nonzero(weights(np.arange(256))) > 0
