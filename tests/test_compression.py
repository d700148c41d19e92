"""int8 gradient compression: quantization bounds + the compressed pod-reduce."""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.optim.compression import dequantize_int8, quantize_int8

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16), n=st.integers(1, 5000),
       scale=st.floats(1e-6, 1e4))
def test_quantize_roundtrip_bound(seed, n, scale):
    """|x - dq(q(x))| <= blockmax/254 elementwise (half a quant step)."""
    x = scale * jax.random.normal(jax.random.key(seed), (n,))
    q, s = quantize_int8(x, block=256)
    back = dequantize_int8(q, s, x.shape, x.dtype)
    blocks = np.asarray(jnp.pad(x, (0, (-n) % 256)).reshape(-1, 256))
    bound = np.repeat(np.abs(blocks).max(1) / 254.0, 256)[:n] + 1e-7
    assert (np.abs(np.asarray(back) - np.asarray(x)) <= bound).all()


def test_quantize_preserves_zeros_and_extremes():
    x = jnp.asarray([0.0, 1.0, -1.0, 127.0, -127.0])
    q, s = quantize_int8(x, block=8)
    back = dequantize_int8(q, s, x.shape, x.dtype)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), rtol=1e-2)
    assert float(back[0]) == 0.0


def test_compressed_psum_subprocess():
    """compressed_psum over a real 4-way 'pod' axis ~= exact psum; and the
    compressed train step lowers+compiles on a (pod, data, model) mesh."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_auto_mesh
from repro.optim.compression import compressed_psum

mesh = make_auto_mesh((4, 2), ("pod", "data"))
x = jax.random.normal(jax.random.key(0), (4, 64))

def f(x):
    comp = compressed_psum(x, "pod")
    exact = jax.lax.psum(x, "pod")
    return comp, exact

g = jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                  out_specs=(P("pod"), P("pod")), check_vma=False)
comp, exact = g(x)
err = float(jnp.max(jnp.abs(comp - exact)))
scale = float(jnp.max(jnp.abs(exact))) + 1e-9
assert err / scale < 0.05, (err, scale)

# the compressed train step (shard_map manual over the pod axis only)
# lowers + compiles on a (pod, data, model) mesh
from repro import configs
from repro.optim.adamw import OptConfig
from repro.train import step as sm
cfg = configs.reduced_config("smollm-135m").replace(n_layers=2)
mesh3 = make_auto_mesh((2, 2, 2), ("pod", "data", "model"))
step = sm.make_train_step_compressed(cfg, OptConfig(), mesh3)
state = sm.abstract_state(cfg)
batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32),
         "mask": jax.ShapeDtypeStruct((8, 32), jnp.float32)}
compiled = jax.jit(step).lower(state, batch).compile()
txt = compiled.as_text()
assert "all-gather" in txt  # the int8 wire path
assert "s8[" in txt, "int8 payload missing from the compiled module"
print("OK", err / scale)
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
