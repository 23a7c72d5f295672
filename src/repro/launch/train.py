"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --technique F+R+Z3 --steps 100 --reduced

Full-size configs + the production mesh are exercised through dryrun.py on
this CPU box; on a real TPU deployment this same entry point runs them by
dropping --reduced (the mesh factory sizes itself to jax.devices()).
"""
import argparse

import jax

from repro.configs import get_config, list_archs
from repro.core.config import SHAPES, ShapeSpec, technique_from_label
from repro.core.trainer import Trainer, TrainerConfig
from repro.launch.jaxcache import use_compile_cache
from repro.launch.mesh import make_local_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b",
                    choices=list_archs() + ["all"])
    ap.add_argument("--technique", default="F+R+Z3")
    ap.add_argument("--shape", default=None, choices=[None] + list(SHAPES))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--resume", default="none", choices=["none", "auto"])
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="model-axis size for a local mesh (1 = no mesh)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    shape = (SHAPES[args.shape] if args.shape
             else ShapeSpec("cli", args.seq, args.batch, "train"))
    technique = technique_from_label(args.technique)
    mesh = (make_local_mesh(model=args.mesh_model)
            if args.mesh_model > 1 or len(jax.devices()) > 1 else None)
    trainer = Trainer(cfg, shape, technique,
                      TrainerConfig(steps=args.steps,
                                    checkpoint_dir=args.checkpoint_dir,
                                    resume=args.resume),
                      mesh=mesh)
    out = trainer.run()
    for h in out["history"]:
        print(f"step {h['step']:>6d}  loss {h['loss']:.4f}")
    print(f"{out['tokens_per_s']:.0f} tokens/s, {out['step_ms']:.1f} ms/step")


if __name__ == "__main__":
    main()
