"""Train a ~100M-param llama-family model for a few hundred steps on the
synthetic pipeline, with async checkpoints + crash-resume.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import dataclasses

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import get_model
from repro.training.data import DataConfig
from repro.training.train_loop import TrainConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    args = ap.parse_args()
    use_compile_cache()

    cfg = dataclasses.replace(
        get_config("llama3-8b"), name="llama-100m",
        n_layers=args.layers, d_model=args.d_model, n_heads=8, n_kv_heads=4,
        d_head=args.d_model // 8, d_ff=args.d_model * 4, vocab=8192,
        max_context=1024)
    model = get_model(cfg)
    print(f"params: {model.param_count()/1e6:.1f}M")
    tc = TrainConfig(steps=args.steps, checkpoint_every=100, log_every=20,
                     ckpt_dir="checkpoints/train_lm")
    dc = DataConfig(vocab=cfg.vocab, seq_len=256, global_batch=8)
    _, _, losses = train(model, cfg, tc, dc)
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
