"""Training driver: the xLSTM-125M assigned architecture on the synthetic
LM pipeline, with checkpoint/restart fault tolerance.

Full-size run (125M params, a few hundred steps) is sized for a real
accelerator; --tiny runs the reduced config end-to-end on CPU in ~a minute,
exercising the identical code path (scan-over-layers, remat, AdamW,
atomic checkpoints, crash-resume).

    PYTHONPATH=src python examples/train_100m.py --tiny --steps 60
    PYTHONPATH=src python examples/train_100m.py --steps 300   # 125M
"""
import argparse

from repro import compile_cache
from repro.configs import ARCHS
from repro.training.data import DataConfig
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import TrainConfig, Trainer


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt", default="/tmp/repro_train100m")
    ap.add_argument("--crash-at", type=int, default=0,
                    help="simulate a crash after N steps, then resume")
    args = ap.parse_args()

    cfg = ARCHS["xlstm-125m"].reduced() if args.tiny \
        else ARCHS["xlstm-125m"]
    dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch)
    ocfg = AdamWConfig(lr=3e-3 if args.tiny else 6e-4, warmup_steps=10,
                       total_steps=args.steps)
    print(f"arch={cfg.name} params~{cfg.num_params()/1e6:.1f}M "
          f"tokens/step={dc.batch * dc.seq_len}")

    if args.crash_at:
        print(f"-- phase 1: train to step {args.crash_at}, then 'crash'")
        t = Trainer(cfg, dc, TrainConfig(steps=args.crash_at,
                                         ckpt_every=max(args.crash_at // 2,
                                                        1),
                                         ckpt_dir=args.ckpt,
                                         log_every=10), ocfg)
        t.run()
        print("-- phase 2: restart, resume from latest checkpoint")

    t = Trainer(cfg, dc, TrainConfig(steps=args.steps,
                                     ckpt_every=max(args.steps // 4, 1),
                                     ckpt_dir=args.ckpt, log_every=10),
                ocfg)
    result = t.run()
    if result["resumed_from"]:
        print(f"resumed from step {result['resumed_from']}")
    first = result["history"][0]["loss"] if result["history"] else None
    last = result["history"][-1]["loss"] if result["history"] else None
    print(f"loss {first:.4f} -> {last:.4f} over "
          f"{args.steps - result['resumed_from']} steps "
          f"({result['wall_s']:.1f}s)")
    if not result["resumed_from"] and args.steps >= 60:
        assert last < first, "training must reduce loss on structured data"


if __name__ == "__main__":
    main()
