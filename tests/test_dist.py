"""Distribution: sharding rules, gradient compression, fault tolerance."""
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import compress, fault
from repro.dist.sharding import RULES, spec_for
from jax.sharding import PartitionSpec as P


class FakeMesh:
    def __init__(self, shape):
        self._shape = shape

    @property
    def shape(self):
        return dict(self._shape)


def test_spec_for_basic_rules():
    mesh = FakeMesh({"data": 16, "model": 16})
    assert spec_for((256, 4096), ("batch", "seq"), mesh) == P("data", None)
    assert spec_for((8192, 64, 128), ("embed", "heads", "none"), mesh) == \
        P("data", "model", None)


def test_spec_for_kv_fallback_to_head_dim():
    mesh = FakeMesh({"data": 16, "model": 16})
    # 8 kv heads don't divide 16 -> head_dim (128) takes the model axis
    assert spec_for((8192, 8, 128), ("embed", "kv_heads", "head_dim"),
                    mesh) == P("data", None, "model")
    # 16-divisible kv heads claim the axis; head_dim then stays unsharded
    assert spec_for((8192, 32, 128), ("embed", "kv_heads", "head_dim"),
                    mesh) == P("data", "model", None)


def test_spec_for_batch_one_replicates():
    mesh = FakeMesh({"data": 16, "model": 16})
    assert spec_for((1, 1, 4096), ("batch", "seq", "none"), mesh) == \
        P(None, None, None)


def test_spec_for_multipod_batch():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert spec_for((256, 4096), ("batch", "seq"), mesh) == \
        P(("pod", "data"), None)


def test_no_axis_reuse_within_tensor():
    mesh = FakeMesh({"data": 16, "model": 16})
    sp = spec_for((256, 16, 16), ("batch", "heads", "mlp"), mesh)
    used = [a for a in jax.tree.leaves(tuple(sp)) if a]
    assert len(used) == len(set(used))


# --- gradient compression -----------------------------------------------------

def test_quantize_dequantize_error_bound():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (5000,)) * 3.0
    codes, scale = compress._quantize(x)
    back = compress._dequantize(codes, scale, x.shape[0])
    # per-chunk max/127 error bound
    assert float(jnp.max(jnp.abs(back - x))) <= float(scale.max()) * 0.5 + 1e-6


def test_error_feedback_accumulates_residual():
    g = jnp.asarray([1e-4] * compress._CHUNK)  # tiny vs chunk scale
    ef = jnp.zeros((compress._CHUNK,))
    codes, scale, new_ef, n = compress.compress_leaf(g, ef)
    # residual carries what quantization dropped
    deq = compress._dequantize(codes, scale, n)
    np.testing.assert_allclose(new_ef, g - deq, atol=1e-9)


COMPRESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.dist import compress

    mesh = jax.make_mesh((8,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    key = jax.random.PRNGKey(0)
    grads = jax.random.normal(key, (8, 4096))      # one row per pod
    ef = jnp.zeros((8, 4096))

    def fn(g, e):
        out, new_e = compress.psum_int8_error_feedback(
            {"w": g[0]}, {"w": e[0].reshape(-1)}, axis="pod")
        return out["w"][None], new_e["w"][None]

    out, new_ef = jax.shard_map(fn, mesh=mesh,
                                in_specs=(P("pod"), P("pod")),
                                out_specs=(P("pod"), P("pod")),
                                check_vma=False)(grads, ef)
    want = grads.mean(axis=0)
    got = out[0]
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.02, rel
    # rows agree (it was an all-reduce)
    np.testing.assert_allclose(out[0], out[7], atol=1e-6)
    print("COMPRESS_OK", rel)
""")


@pytest.mark.slow
def test_int8_allreduce_via_shard_map(tmp_path):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "../src"))
    script = str(tmp_path / "c.py")
    with open(script, "w") as f:
        f.write(COMPRESS_SCRIPT)
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COMPRESS_OK" in out.stdout


# --- fault tolerance ------------------------------------------------------------

def test_preemption_handler_flag():
    h = fault.PreemptionHandler(install=False)
    assert not h.should_stop
    h.trigger()
    assert h.should_stop


def test_step_monitor_detects_straggler():
    mon = fault.StepMonitor(window=20, threshold=2.0)
    for i in range(15):
        mon.start_step(i)
        mon.times.append(0.01)  # fabricate quick steps
        mon.times.pop(0) if len(mon.times) > 20 else None
    mon.start_step(99)
    time.sleep(0.05)
    inc = mon.end_step()
    assert inc is not None and inc.step == 99
    assert mon.incidents
