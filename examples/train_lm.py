"""End-to-end LM training driver: a ~100M-parameter qwen2-family model for a
few hundred steps, with checkpoint/restart.

Default runs a reduced model so the example finishes on this CPU container;
pass --full for the 100M × 300-step configuration (sized for a real
accelerator), --arch to pick any assigned architecture's smoke config.

    PYTHONPATH=src python examples/train_lm.py --steps 50
"""
import argparse

import jax

from repro.launch.train import TrainConfig, Trainer
from repro.models import transformer_lm as lm
from repro.runtime import enable_compile_cache


def model_100m():
    # ~100M params: 12L, d=768, 12H, ff=2048, vocab=32768
    return lm.LMConfig(
        name="lm-100m", n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
        d_head=64, d_ff=2048, vocab=32768, dtype="bfloat16",
    )


def model_tiny():
    return lm.LMConfig(
        name="lm-tiny", n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
        d_head=32, d_ff=512, vocab=1024, dtype="float32", kv_block=64,
    )


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = model_100m() if args.full else model_tiny()
    steps = 300 if args.full else args.steps
    n_params = None

    def make_batch(step):
        # synthetic "shifted-window" language data: next token = (t*7+3) % V,
        # learnable structure so the loss visibly drops
        k = jax.random.PRNGKey(step)
        toks = jax.random.randint(k, (args.batch, args.seq), 0, cfg.vocab)
        targets = (toks * 7 + 3) % cfg.vocab
        return {"tokens": toks, "targets": targets}

    tc = TrainConfig(steps=steps, ckpt_every=max(steps // 4, 10), warmup=10,
                     log_every=max(steps // 10, 1))
    trainer = Trainer(lm, cfg, train_cfg=tc)
    params, _, hist = trainer.fit(make_batch, ckpt_dir=args.ckpt_dir, steps=steps)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {cfg.name}  params={n_params/1e6:.1f}M")
    for h in hist:
        print(f"step {h['step']:4d}  loss {h['loss']:.4f}  "
              f"gnorm {h['grad_norm']:.2f}  {h['sec_per_step']:.2f}s/step")
    assert hist[-1]["loss"] < hist[0]["loss"], "loss should decrease"
    print("done — loss decreased from "
          f"{hist[0]['loss']:.3f} to {hist[-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
