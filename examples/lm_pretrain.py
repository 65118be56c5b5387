"""End-to-end driver: pretrain a ~100M-param LM for a few hundred steps with
the fault-tolerant trainer (checkpoints, resume, metrics log).

    PYTHONPATH=src python examples/lm_pretrain.py --steps 300

Uses a llama3-family config scaled to ~100M params; the data stream has
bigram structure, so the loss drop is meaningful (≈ ln(vocab) → much lower).
"""
import argparse
import dataclasses

from repro import configs
from repro.launch.mesh import make_mesh
from repro.launch.train import synthetic_data
from repro.optim import AdamConfig
from repro.training import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_lm100m")
    args = ap.parse_args()

    # ~100M params: 12L, d=768, llama3 family (GQA, RoPE, SwiGLU)
    cfg = dataclasses.replace(
        configs.get("llama3-8b"), n_layers=12, d_model=768, n_heads=12,
        n_kv_heads=4, d_ff=2048, vocab=8192, head_dim=64, dtype="float32")
    print(f"params ≈ {cfg.param_count()/1e6:.0f}M")
    mesh = make_mesh((1, 1), ("data", "model"))
    tcfg = TrainerConfig(total_steps=args.steps, checkpoint_every=100,
                         log_every=20, checkpoint_dir=args.checkpoint_dir)
    trainer = Trainer(cfg, mesh, AdamConfig(lr=3e-4, grad_clip=1.0), tcfg)
    data = synthetic_data(cfg, batch=8, seq=256)
    trainer.fit(data, on_metrics=lambda s, rec: print(
        f"step {s}: loss {rec['loss']:.4f}", flush=True))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
