"""Pallas kernel numerics tests (interpret mode on the CPU mesh).

Mosaic compiles the kernels only on a TPU: chip_smoke.py checks each
against its XLA reference there; nothing in this CPU test tree runs
them compiled."""

import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.kv.updaters import Ftrl
from parameter_server_tpu.ops.pallas_kernels import (
    _pad_to_tiles,
    _unpad,
    ftrl_delta_pallas,
    quantize_stochastic_pallas,
)


@pytest.fixture()
def interpret_mode():
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        yield


class TestPadding:
    @pytest.mark.parametrize("shape", [(5,), (1000, 3), (1024, 1), (8, 128)])
    def test_pad_unpad_roundtrip(self, shape, rng):
        x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        m, n = _pad_to_tiles(x)
        assert m.shape[1] == 128 and m.shape[0] % 8 == 0
        np.testing.assert_array_equal(np.asarray(_unpad(m, n, shape)), np.asarray(x))


class TestFtrlKernel:
    def test_matches_jnp_delta(self, interpret_mode, rng):
        z = jnp.asarray(rng.normal(size=(300, 2)).astype(np.float32))
        n = jnp.asarray(np.abs(rng.normal(size=(300, 2))).astype(np.float32))
        g = jnp.asarray(rng.normal(size=(300, 2)).astype(np.float32))
        up = Ftrl(alpha=0.3, beta=1.0, lambda_l1=0.5, lambda_l2=0.1)
        ref = up.delta({"z": z, "n": n}, g)
        dz, dn = ftrl_delta_pallas(
            z, n, g, alpha=0.3, beta=1.0, l1=0.5, l2=0.1
        )
        np.testing.assert_allclose(np.asarray(dz), np.asarray(ref["z"]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(dn), np.asarray(ref["n"]), atol=1e-6)

    def test_use_pallas_flag_raises_off_tpu(self):
        """Asking for the kernel on a backend that cannot run it raises
        instead of quietly computing the jnp composite."""
        rows = {"z": jnp.ones((4, 1)), "n": jnp.ones((4, 1))}
        with pytest.raises(RuntimeError, match="needs a TPU backend"):
            Ftrl(use_pallas=True).delta(rows, jnp.ones((4, 1)))


class TestQuantizeKernel:
    def test_roundtrip_within_scale(self, interpret_mode, rng):
        x = jnp.asarray(rng.normal(size=(700,)).astype(np.float32)) * 4
        q, lo, scale = quantize_stochastic_pallas(0, x, num_bytes=1)
        assert q.dtype == jnp.int8
        dec = (q.astype(jnp.float32) + 127) * scale + lo
        assert float(jnp.max(jnp.abs(dec - x))) <= float(scale) + 1e-6

    def test_int16(self, interpret_mode, rng):
        x = jnp.asarray(rng.normal(size=(700,)).astype(np.float32))
        q, lo, scale = quantize_stochastic_pallas(1, x, num_bytes=2)
        assert q.dtype == jnp.int16
        dec = (q.astype(jnp.float32) + 32767) * scale + lo
        assert float(jnp.max(jnp.abs(dec - x))) <= float(scale) + 1e-6
