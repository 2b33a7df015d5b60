"""Production training launcher: LookaheadKV module training under pjit on
whatever mesh is available (full production meshes on TPU; a host mesh on
CPU for verification).

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
        --steps 40 --batch 4

Data sources:

* default — the synthetic mixture (``data/synthetic.MixtureIterator``),
  running the full two-pass objective (GT pass + lookahead pass) per step;
* ``--harvest <dir>`` — distillation against gt_oracle targets harvested
  from serving traces (``python -m repro.data.harvest``): each step runs
  only the lookahead pass against the stored score vectors.

Checkpointing: ``--ckpt-every N`` writes the full trainer state
``{"lkv", "opt"}`` (modules + AdamState) every N steps; ``--resume`` picks
up from the last save — step count, optimizer moments and the data stream
position all continue, so a killed run replays bit-identically.
``--verify`` turns the run into the CI train-smoke gate: the loss must
decrease and the final checkpoint must round-trip through
``ckpt.load(like=...)`` bit-exactly.

On a real v5e deployment this same entry point runs with
``--mesh pod|multipod`` (requires the matching device count).
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import io as ckpt
from repro.common import sharding as sh
from repro.common.compile_cache import enable_compile_cache
from repro.common.config import TrainConfig
from repro.configs import get_config, get_smoke_config
from repro.core.lookahead import init_lookahead_params
from repro.data import harvest, synthetic
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer as tf
from repro.optim import adam


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="host",
                    choices=["host", "pod", "multipod"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--n-in", type=int, default=64)
    ap.add_argument("--n-out", type=int, default=12)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="experiments/ckpt/train_lkv.npz")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--harvest", default="",
                    help="distill against a harvested gt_oracle dataset "
                         "directory instead of the synthetic mixture")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="write the trainer state (modules + AdamState) "
                         "every N steps (0: final save only)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from --ckpt if it exists (step count, "
                         "optimizer moments and data position resume)")
    ap.add_argument("--verify", action="store_true",
                    help="CI train-smoke gate: assert the loss decreased "
                         "and the checkpoint round-trips bit-exactly")
    # kill simulation for the resume test: exit (no final save) after N
    # steps, as if the process died mid-run
    ap.add_argument("--stop-after", type=int, default=0,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if not cfg.technique_applies:
        raise SystemExit(f"{args.arch}: technique inapplicable (DESIGN.md §5)"
                         " — use examples/train_e2e.py --lm for LM training")
    mesh = (make_host_mesh() if args.mesh == "host"
            else make_production_mesh(multi_pod=args.mesh == "multipod"))
    tc = TrainConfig(steps=args.steps, lr=args.lr, batch_size=args.batch,
                     n_in=args.n_in, n_out=args.n_out, seed=args.seed)

    key = jax.random.PRNGKey(args.seed)
    with mesh:
        params = tf.init_params(key, cfg)
        pspecs = sh.param_specs(cfg, mesh)
        params = jax.device_put(
            params, jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs))
        lkv = init_lookahead_params(jax.random.PRNGKey(args.seed + 1), cfg,
                                    params["layers"])
        lkv = jax.device_put(lkv, NamedSharding(mesh, P()))
        opt = adam.init(lkv)

        start = 0
        if args.resume and os.path.exists(args.ckpt):
            state = ckpt.load(args.ckpt, like={"lkv": lkv, "opt": opt})
            lkv = jax.device_put(state["lkv"], NamedSharding(mesh, P()))
            opt = jax.device_put(state["opt"], NamedSharding(mesh, P()))
            start = int(ckpt.metadata(args.ckpt).get("step", 0))
            print(f"resumed {args.ckpt} at step {start}", flush=True)

        if args.harvest:
            it = harvest.HarvestIterator(args.harvest, args.batch,
                                         seed=args.seed)
            step_fn = jax.jit(steps_mod.make_distill_step(cfg, tc))
        else:
            it = synthetic.MixtureIterator(cfg, args.batch, args.n_in,
                                           args.n_out, seed=args.seed)
            step_fn = jax.jit(steps_mod.make_train_step(cfg, tc))
        # both iterators are pure functions of (seed, draw index), so the
        # resumed data stream continues exactly where the killed run left it
        for _ in range(start):
            next(it)

        def save_state(step: int) -> None:
            ckpt.save(args.ckpt,
                      {"lkv": jax.device_get(lkv),
                       "opt": jax.device_get(opt)},
                      metadata={"arch": cfg.name, "step": step,
                                "steps": args.steps,
                                "source": args.harvest or "synthetic"})

        dp = sh.batch_axes(mesh)
        losses = []
        t0 = time.time()
        for i in range(start, args.steps):
            b = next(it)
            if args.harvest:
                batch = {
                    "x": jax.device_put(jnp.asarray(b["x"]),
                                        NamedSharding(mesh, P(dp, None))),
                    "s_gt": jax.device_put(
                        jnp.asarray(b["s_gt"]),
                        NamedSharding(mesh, P(None, dp))),
                }
            else:
                x = jnp.asarray(b.x)
                xy = jnp.concatenate([x, jnp.asarray(b.y)], axis=1)
                batch = jax.device_put({"x": x, "xy": xy},
                                       NamedSharding(mesh, P(dp, None)))
            lkv, opt, loss = step_fn(params, lkv, opt, batch)
            losses.append(float(loss))
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:4d}  loss {losses[-1]:.4f}  "
                      f"({time.time()-t0:.0f}s)", flush=True)
            if (args.ckpt_every and (i + 1) % args.ckpt_every == 0
                    and (i + 1) < args.steps):
                save_state(i + 1)
            if args.stop_after and (i + 1) >= args.stop_after:
                print(f"stopped after step {i + 1} (simulated kill)")
                return {"losses": losses, "ckpt": args.ckpt,
                        "step": i + 1}
    save_state(args.steps)
    print(f"saved -> {args.ckpt}")

    if args.verify:
        assert len(losses) >= 2 and min(losses[1:]) < losses[0], \
            f"train-smoke: loss did not decrease ({losses[0]:.4f} -> " \
            f"{min(losses[1:]):.4f})"
        back = ckpt.load(args.ckpt, like={"lkv": lkv, "opt": opt})
        for a, b in zip(jax.tree.leaves({"lkv": lkv, "opt": opt}),
                        jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                          np.asarray(b))
        meta = ckpt.metadata(args.ckpt)
        assert meta["step"] == args.steps, meta
        print(f"train-smoke verdict: PASS (loss {losses[0]:.4f} -> "
              f"{losses[-1]:.4f}, checkpoint round-trips bit-exactly)")
    return {"losses": losses, "ckpt": args.ckpt, "step": args.steps}


if __name__ == "__main__":
    # only as a program: tests call main() and keep JAX's defaults
    enable_compile_cache()
    main()
