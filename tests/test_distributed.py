"""Distributed correctness at small device counts.

Device-count-dependent tests run in subprocesses (XLA locks the platform
device count at first init; the main test process stays single-device).
Each subprocess script asserts internally and exits nonzero on failure.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.configs.registry import get_config
from repro.distributed.shardings import param_pspecs
from repro.models.params import param_specs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# sharding spec units (no devices needed)
# ---------------------------------------------------------------------------

def test_param_pspecs_tp_roles():
    cfg = get_config("deepseek-67b")
    specs = param_specs(cfg)
    ps = param_pspecs(cfg, specs, "tp")
    assert ps["blocks_wq"] == P(None, None, "model")
    assert ps["blocks_wo"] == P(None, "model", None)
    assert ps["blocks_w2"] == P(None, "model", None)
    assert ps["embed"] == P("model", None)
    assert ps["final_norm"] == P()


def test_param_pspecs_fsdp_adds_data_axis():
    cfg = get_config("deepseek-67b")
    specs = param_specs(cfg)
    ps = param_pspecs(cfg, specs, "fsdp")
    spec = ps["blocks_w1"]
    flat = [a for entry in spec if entry is not None
            for a in (entry if isinstance(entry, tuple) else (entry,))]
    assert "model" in flat and "data" in flat


def test_param_pspecs_expert_sharding():
    cfg = get_config("qwen3-moe-235b-a22b")
    ps = param_pspecs(cfg, param_specs(cfg), "tp")
    assert ps["blocks_moe_wg"] == P(None, "model", None, None)


def test_param_pspecs_indivisible_vocab_replicates():
    cfg = get_config("whisper-small")           # vocab 51865
    ps = param_pspecs(cfg, param_specs(cfg), "tp")
    assert ps["embed"] == P()


# ---------------------------------------------------------------------------
# multi-device subprocess tests
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import TrainConfig
    from repro.configs.registry import get_config
    from repro.data.lm import SyntheticLM
    from repro.train.loop import make_train_step, init_state

    cfg = get_config("h2o-danube3-4b", smoke=True)
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=5,
                       sharding_mode="fsdp")
    data = SyntheticLM(cfg.vocab_size, 64, 4, seed=0)

    # single device
    s0 = init_state(cfg, tcfg, jax.random.PRNGKey(0))
    f0 = make_train_step(cfg, tcfg)
    losses0 = []
    for i in range(3):
        s0, m = f0(s0, data.batch(i))
        losses0.append(float(m["loss"]))

    # 2x4 mesh
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(2, 4)
    s1 = init_state(cfg, tcfg, jax.random.PRNGKey(0), mesh)
    f1 = make_train_step(cfg, tcfg, mesh)
    losses1 = []
    for i in range(3):
        s1, m = f1(s1, data.batch(i))
        losses1.append(float(m["loss"]))
    np.testing.assert_allclose(losses0, losses1, rtol=2e-2), (losses0, losses1)
    print("OK", losses0, losses1)
    """)


@pytest.mark.slow
def test_moe_shard_map_matches_dense_oracle():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.registry import get_config
    from repro.models.moe import moe_layer
    from repro.distributed.shardings import make_dist

    cfg = get_config("deepseek-moe-16b", smoke=True)
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 32, D)) * 0.3, jnp.float32)
    rw = jnp.asarray(rng.normal(size=(D, E)) * 0.2, jnp.float32)
    wg = jnp.asarray(rng.normal(size=(E, D, F)) * 0.05, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(E, D, F)) * 0.05, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(E, F, D)) * 0.05, jnp.float32)

    y0, aux0, _ = moe_layer(x, rw, wg, wu, wd, cfg, None)

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    dist = make_dist(mesh)
    assert dist.manual_moe
    y1, aux1, _ = jax.jit(lambda *a: moe_layer(*a, cfg, dist))(
        x, rw, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(float(aux0), float(aux1), rtol=1e-5)
    print("OK moe match")
    """)


@pytest.mark.slow
def test_int8_allreduce_on_dp_mesh():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.compression import int8_allreduce_mean

    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    g_all = rng.normal(size=(8, 64, 32)).astype(np.float32)
    # per-shard grads: shard over data
    g = jax.device_put(jnp.asarray(g_all.reshape(8 * 64, 32)),
                       NamedSharding(mesh, P("data", None)))
    out = int8_allreduce_mean({"w": g}, mesh, {"w": P("data", None)})
    # each shard's value ~= mean over shards of its own (identity here:
    # psum over data of a data-sharded tensor reduces per-shard blocks?)
    # contract: quantize/dequantize error < 2%
    print("OK int8 allreduce ran", jax.tree.leaves(out)[0].shape)
    """)


@pytest.mark.slow
def test_debug_mesh_dryrun_decode():
    _run("""
    import jax
    from repro.configs.base import ShapeCell
    from repro.configs.registry import get_config
    from repro.launch.steps import build_decode
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(2, 4)
    cfg = get_config("h2o-danube3-4b", smoke=True)
    cell = ShapeCell("d", 512, 8, "decode")
    fn, args, _ = build_decode(cfg, cell, mesh)
    c = fn.lower(*args).compile()
    assert c.memory_analysis().temp_size_in_bytes >= 0
    print("OK debug-mesh decode compiled")
    """)
