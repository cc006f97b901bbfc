"""Multi-device integration tests (subprocess-forced host devices).

These run small sharded programs on 8 forced CPU devices in a
subprocess (the main pytest process must keep 1 device), validating:

* FSDP+TP train step == single-device train step numerically,
* the serve-mode decode step compiles + runs under a mesh,
* the int8 compressed all-reduce inside shard_map,
* a reduced end-to-end dry-run cell (lower+compile+cost/memory record).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_train_matches_single_device():
    print(_run("""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_test_mesh
    from repro.models import build_model
    from repro.parallel.sharding import param_shardings, use_mesh
    from repro.train import TrainConfig, init_train_state, make_train_step

    cfg = get_config("olmo-1b", smoke=True)
    model = build_model(cfg)
    step = make_train_step(cfg, TrainConfig(remat=False, microbatches=1))
    state = init_train_state(model, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32),
                                          0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 32),
                                          0, cfg.vocab_size)}
    # single device reference
    s1, m1 = jax.jit(step)(state, batch)
    # sharded (data=4, model=2)
    mesh = make_test_mesh((4, 2), ("data", "model"))
    sh = param_shardings(mesh, state)
    with use_mesh(mesh):
        sharded = jax.jit(step, in_shardings=(sh, None),
                          out_shardings=(sh, None))
        s2, m2 = sharded(jax.device_put(state, sh), batch)
    l1, l2 = float(m1["loss"]), float(m2["loss"])
    assert abs(l1 - l2) / l1 < 2e-2, (l1, l2)
    import numpy as np
    a = np.asarray(s1.params["embed"]["tok"], dtype=np.float32)
    b = np.asarray(s2.params["embed"]["tok"], dtype=np.float32)
    err = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
    assert err < 5e-2, err
    print("sharded==single OK", l1, l2)
    """))


def test_sharded_decode_step():
    print(_run("""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_test_mesh
    from repro.models import build_model
    from repro.models.transformer import init_cache, lm_decode_step
    from repro.parallel.sharding import (cache_shardings, param_shardings,
                                         use_mesh)

    cfg = get_config("qwen2.5-1.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = jnp.array([1, 2, 3, 4], jnp.int32)
    cache = init_cache(cfg, 4, 64)
    ref_logits, _ = lm_decode_step(params, cfg, cache, tok)

    mesh = make_test_mesh((4, 2), ("data", "model"))
    p_sh = param_shardings(mesh, params, mode="serve")
    c_sh = cache_shardings(mesh, cache)
    with use_mesh(mesh, mode="serve"):
        f = jax.jit(lambda p, c, t: lm_decode_step(p, cfg, c, t),
                    in_shardings=(p_sh, c_sh, None))
        logits, new_cache = f(jax.device_put(params, p_sh),
                              jax.device_put(cache, c_sh), tok)
    import numpy as np
    err = np.max(np.abs(np.asarray(logits[:, :cfg.vocab_size])
                        - np.asarray(ref_logits[:, :cfg.vocab_size])))
    assert err < 1e-2, err
    assert int(new_cache["len"][0]) == 1
    print("sharded decode OK", float(err))
    """))


def test_compressed_psum_shard_map():
    print(_run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_test_mesh
    from repro.optim.compression import compressed_psum

    mesh = make_test_mesh((2, 4), ("pod", "data"))
    g = jax.random.normal(jax.random.PRNGKey(0), (2, 4096)) * 1e-3

    def f(gl):
        mean, resid = compressed_psum(gl[0], axis="pod")
        return mean[None], resid[None]

    mean, resid = jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                                out_specs=P("pod"))(g)
    exact = jnp.mean(g, axis=0)
    err = float(jnp.max(jnp.abs(mean[0] - exact)) /
                (jnp.max(jnp.abs(exact)) + 1e-12))
    assert err < 0.02, err
    print("compressed psum OK", err)
    """))


def test_dryrun_single_cell_production_mesh():
    """Full run_cell end to end (512 forced devices, whisper train cell):
    proves the dry-run path lowers, compiles, fits, and records costs."""
    out = _run("""
    import os
    os.environ["REPRO_DRYRUN_DEVICES"] = "512"
    os.environ["REPRO_MICROBATCHES"] = "16"
    from repro.launch import dryrun
    rec = dryrun.run_cell("whisper-base", "train_4k", False,
                          cost_pass=False, verbose=False)
    assert rec["fits_16g"], rec
    assert rec["hlo_flops"] > 0 and rec["collective_bytes"] > 0
    assert rec["chips"] == 256
    print("dryrun cell OK", rec["bytes_per_device"])
    """, devices=1, timeout=560)
    assert "dryrun cell OK" in out
