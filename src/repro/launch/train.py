"""Training launcher.

On a real TPU pod each host runs this same script (jax.distributed
initializes from the TPU environment); on the CPU container it runs the
reduced config on the host mesh — same code path, different mesh.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-14b \
        --steps 100 --reduced --ckpt /tmp/ckpt
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-72b \
        --mesh single          # full config on the 16x16 mesh (TPU)
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro import util
from repro.configs.base import get_arch, reduced as reduce_cfg
from repro.data.pipeline import DataConfig, make_batch
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import frontends
from repro.models.model import build_model
from repro.optim import adamw
from repro.train import fault
from repro.train.trainer import TrainConfig, Trainer


def main():
    util.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-sized config of the same family")
    ap.add_argument("--score-backend", default=None,
                    help="registered ScoreBackend name (overrides the "
                         "arch's score_mode)")
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--grad-compress", action="store_true",
                    help="int8+EF gradient compression (pod axis)")
    ap.add_argument("--bf16-moments", action="store_true")
    args = ap.parse_args()

    if jax.process_count() > 1:          # multi-host TPU: auto-init
        jax.distributed.initialize()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    if args.score_backend:
        from repro.core import score_backend
        score_backend.get_backend(args.score_backend)   # validate early
        cfg = dataclasses.replace(cfg, score_mode=args.score_backend)
    model = build_model(cfg)
    mesh = {"host": make_host_mesh,
            "single": lambda: make_production_mesh(multi_pod=False),
            "multi": lambda: make_production_mesh(multi_pod=True)}[
        args.mesh]()

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.global_batch)

    def data_fn(step):
        b = dict(make_batch(dc, step))
        if cfg.enc_dec:
            b["enc_embeds"] = frontends.audio_frames(
                args.global_batch, 128, cfg.d_model, seed=step)
        elif cfg.frontend == "vision":
            pass                          # text-over-backbone training
        return b

    tc = TrainConfig(
        peak_lr=args.lr, warmup_steps=max(args.steps // 20, 10),
        total_steps=args.steps, microbatches=args.microbatches,
        grad_compress=args.grad_compress,
        adamw=adamw.AdamWConfig(
            moment_dtype="bfloat16" if args.bf16_moments else "float32"),
        ckpt_every=max(args.steps // 5, 50))
    trainer = Trainer(model, tc, data_fn, ckpt_dir=args.ckpt, mesh=mesh)
    fault.install(trainer)
    _, _, hist = trainer.run()
    print(f"[train] done: loss {hist[0]['loss']:.4f} -> "
          f"{hist[-1]['loss']:.4f}; skipped {trainer.skipped_steps}")


if __name__ == "__main__":
    main()
