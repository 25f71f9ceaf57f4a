"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant elastic loop on the selected architecture. With
``--smoke`` (default) the reduced config runs on local devices; without it
the full assigned config is used (expects a real TPU pod).
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import ARCHS, SHAPES, get_config, get_smoke_config
from repro.configs.base import ParallelConfig, RunConfig, ShapeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.train.loop import train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCHS))
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--ckpt-dir", default="/tmp/repro-train")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--data", type=int, default=0,
                    help="data-axis size (0 = all local devices)")
    ap.add_argument("--model-axis", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        cfg = get_smoke_config(args.arch)
        shape = ShapeConfig("smoke", "train", 64, 8)
        parallel = ParallelConfig(attn_q_chunk=32, attn_kv_chunk=32)
    else:
        cfg = get_config(args.arch)
        shape = SHAPES[args.shape]
        parallel = None  # default_parallel inside the step builder
    n_dev = len(jax.devices())
    data = args.data or max(n_dev // args.model_axis, 1)
    mesh = (make_mesh(data, args.model_axis)
            if data * args.model_axis > 1 else None)
    rcfg = RunConfig(model=cfg, shape=shape,
                     parallel=parallel or ParallelConfig(),
                     total_steps=args.steps)
    print(f"arch={args.arch} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={'1dev' if mesh is None else dict(mesh.shape)}")
    report = train_loop(rcfg, ckpt_dir=args.ckpt_dir, num_steps=args.steps,
                        ckpt_every=args.ckpt_every, mesh=mesh)
    print(f"steps={report.steps_run} restarts={report.restarts} "
          f"loss {report.losses[0]:.3f} -> {report.final_loss:.3f}")


if __name__ == "__main__":
    main()
