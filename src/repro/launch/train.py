"""Training launcher: ``python -m repro.launch.train --arch olmo-1b ...``.

Single-host it runs a real training loop (smoke/reduced or full config);
on a TPU slice the same script runs under the production mesh with the
FSDP+TP shardings of ``repro.parallel`` (``--mesh data,model``).  Wires
in the data pipeline, async checkpointing, straggler monitor, and
resume-from-latest.
"""

from __future__ import annotations

import argparse
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.checkpoint import AsyncCheckpointer, restore_latest
from repro.configs import get_config
from repro.data import DataConfig, DataLoader
from repro.launch.compile_cache import setup_compilation_cache
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.parallel.sharding import param_shardings, use_mesh
from repro.train import TrainConfig, init_train_state, make_train_step
from repro.train.fault_tolerance import StragglerMonitor


def parse_mesh(spec: Optional[str]) -> Optional[Mesh]:
    """``--mesh`` value -> mesh over the local devices: '4,2' is
    (data=4, model=2), '8' is (data=8); None or '' is no mesh."""
    if not spec:
        return None
    shape = tuple(int(x) for x in spec.split(","))
    return make_mesh(shape, ("data", "model")[:len(shape)])


def init_state(model, mesh: Optional[Mesh]):
    """Train state from seed 0.  Under a mesh it is initialized already
    sharded, so no one device has to hold all of it."""
    key = jax.random.PRNGKey(0)
    init = functools.partial(init_train_state, model)
    if mesh is None:
        return init(key)
    sh = param_shardings(mesh, jax.eval_shape(init, key))
    return jax.jit(init, out_shardings=sh)(key)


def jit_train_step(step_fn, state, mesh: Optional[Mesh]):
    """Jit ``step_fn`` with the train state donated.  Under a mesh the
    state is sharded by the FSDP+TP rules of ``repro.parallel`` and
    placed; call the step inside ``use_mesh(mesh)``.  Returns
    (jitted step, placed state)."""
    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0,)), state
    sh = param_shardings(mesh, state)
    step = jax.jit(step_fn, in_shardings=(sh, None),
                   out_shardings=(sh, None), donate_argnums=(0,))
    return step, jax.device_put(state, sh)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh", default=None,
                    help="e.g. '4,2' -> (data=4, model=2) over local devices")
    args = ap.parse_args(argv)

    setup_compilation_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    tcfg = TrainConfig(
        optimizer=AdamWConfig(lr=args.lr, warmup_steps=20,
                              total_steps=args.steps),
        remat=not args.smoke, microbatches=args.microbatches)
    step_fn = make_train_step(cfg, tcfg)

    mesh = parse_mesh(args.mesh)

    state = init_state(model, mesh)
    start = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = AsyncCheckpointer(args.ckpt_dir)
        got, restored = restore_latest(args.ckpt_dir, state)
        if got is not None:
            start, state = got, restored
            print(f"resumed from step {start}")

    step_fn, state = jit_train_step(step_fn, state, mesh)

    data = DataLoader(DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                                 global_batch=args.batch), start_step=start)
    monitor = StragglerMonitor(n_hosts=jax.process_count())
    t_tokens = args.batch * args.seq

    ctx = use_mesh(mesh) if mesh is not None else _nullctx()
    with ctx:
        t_last = time.time()
        for i, batch in zip(range(start, args.steps), data):
            jb = {k: jnp.asarray(v) for k, v in batch.items()}
            state, metrics = step_fn(state, jb)
            if (i + 1) % args.log_every == 0 or i == start:
                loss = float(metrics["loss"])
                dt = time.time() - t_last
                t_last = time.time()
                monitor.record(jax.process_index(), dt)
                print(f"step {i+1:5d} loss {loss:.4f} "
                      f"({t_tokens * args.log_every / max(dt, 1e-9):,.0f} "
                      f"tok/s) stragglers={monitor.stragglers()}")
            if ckpt and (i + 1) % args.ckpt_every == 0:
                ckpt.save(i + 1, state)
    data.close()
    if ckpt:
        ckpt.save(args.steps, state)
        ckpt.close()
    print("done")


class _nullctx:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
