"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --reduced --steps 100 --batch 8 --seq 128 --mode hier

On the production fleet the same entry point runs under one process per host
(jax.distributed.initialize); on one host it runs single-process with
however many devices the platform exposes.  ``main`` returns the
``TrainReport``.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.topology import MeshTopology
from repro.data.synthetic import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh_from_topo
from repro.runtime.steps import make_train_step
from repro.runtime.train_loop import train


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mode", default="hier", choices=["hier", "naive"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(n_layers=args.n_layers, d_model=args.d_model)

    n_dev = len(jax.devices())
    topo = MeshTopology({"data": n_dev, "model": 1}, slow_axes=())
    mesh = make_mesh_from_topo(topo)
    bundle = make_train_step(cfg, topo, mesh, mode=args.mode, lr=args.lr,
                             compute_dtype=jnp.float32)
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch)
    return train(bundle, steps=args.steps, data_cfg=data_cfg,
                 ckpt_dir=args.ckpt, save_every=args.save_every)


if __name__ == "__main__":
    main()
