"""Int8 error-feedback gradient compression tests.

The ring needs real multi-device SPMD; jax locks the device count at init,
so the 8-device checks run in a subprocess with XLA_FLAGS set."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import compression

REPO = Path(__file__).resolve().parents[1]


def test_quant_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=4096).astype(np.float32))
    q, s = compression._quant(x)
    err = np.abs(np.asarray(q, np.float32) * float(s) - np.asarray(x))
    assert err.max() <= float(s) * 0.5 + 1e-7


def test_single_shard_identity():
    x = jnp.asarray(np.random.default_rng(1).normal(size=100).astype("f4"))
    out = compression.compressed_mean(x, ("data",), (1,))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.optim import compression

    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(8, 1000)).astype(np.float32)

    def body(x):
        return compression.ring_allreduce_int8(x[0], "data", 8) / 8.0

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data", None),
                               out_specs=P(None), check_vma=False))
    got = np.asarray(fn(jnp.asarray(xs)))
    want = xs.mean(0)
    scale = np.abs(xs).max() / 127.0
    err = np.abs(got - want)
    # per-hop requantization noise: bounded by ~n_hops * quant step
    assert err.max() < 40 * scale, (err.max(), scale)
    corr = np.corrcoef(got, want)[0, 1]
    assert corr > 0.999, corr

    # error feedback: repeated sync of the SAME grads converges in mean
    grads = {"w": jnp.asarray(rng.normal(size=(8, 500)).astype(np.float32))}
    def sync_once(g, err):
        def b(gv, ev):
            out, ne = compression.sync_grads({"w": gv[0]}, ev[0],
                                             ("data",), (8,))
            return out["w"], ne[None]
        f = jax.jit(jax.shard_map(
            b, mesh=mesh, in_specs=(P("data", None), P("data", None)),
            out_specs=(P(None), P("data", None)), check_vma=False))
        return f(g, err)
    err_buf = jnp.zeros((8, 500), jnp.bfloat16)
    outs = []
    for _ in range(8):
        o, err_buf = sync_once(grads["w"], err_buf)
        outs.append(np.asarray(o))
    want2 = np.asarray(grads["w"]).mean(0)
    avg = np.mean(outs, axis=0)
    base_err = np.abs(outs[0] - want2).max()
    ef_err = np.abs(avg - want2).max()
    assert ef_err < base_err, (ef_err, base_err)   # EF removes bias over time
    print("OK", err.max(), corr, base_err, ef_err)
""")


def test_ring_allreduce_8dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
