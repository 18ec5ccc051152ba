"""Production training driver.

On a 256-device pod this builds the production mesh, installs sharding
rules, and runs the fault-tolerant loop with sharded inputs.  On fewer
devices it builds a ``(devices, 1)`` data-parallel mesh; ``--reduced``
swaps in a narrow config that runs on the CPU through the same code path.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import os
import tempfile

import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, get_config, reduced_config
from repro.data.lm import LMDataConfig, data_iterator
from repro.distributed.sharding import axis_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh, make_production_mesh, rules_for
from repro.models.registry import build_model
from repro.training.loop import LoopConfig, train_loop


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=ALL_ARCHS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(), "repro_train"))
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    bundle = build_model(cfg)
    n_dev = len(jax.devices())

    if n_dev >= 256:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        rules = rules_for(args.arch, multi_pod=args.multi_pod,
                          global_batch=args.batch)
    else:
        mesh = make_mesh((n_dev, 1), ("data", "model"))
        rules = rules_for(args.arch, multi_pod=False,
                          global_batch=args.batch)

    data_cfg = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                            global_batch=args.batch)
    loop_cfg = LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every)
    print(f"arch={cfg.name} params≈{cfg.param_count()/1e6:.1f}M "
          f"devices={n_dev} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")
    with axis_rules(rules, mesh):
        out = train_loop(bundle,
                         lambda s: data_iterator(data_cfg, s), loop_cfg)
    print(f"done: losses {out['losses'][:2]} -> {out['losses'][-2:]} "
          f"restarts={out['restarts']}")


if __name__ == "__main__":
    main()
