"""LM training example: a reduced-config assigned architecture trained for
a few hundred steps with the full production loop (checkpointing, fault
injection + recovery, straggler mitigation, cosine schedule).

    PYTHONPATH=src python examples/lm_training.py [--steps 200]
"""

import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.data.pipeline import LmDataConfig, lm_token_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.models.moe import MoeConfig
from repro.models.transformer import TransformerConfig, init_params, loss_fn
from repro.optim.adamw import AdamWConfig
from repro.train.fault import FaultInjector
from repro.train.loop import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--moe", action="store_true", help="deepseek-moe-style reduced config")
    args = ap.parse_args()
    enable_compile_cache()

    # Reduced deepseek-moe-16b family config (CPU-sized).
    moe = MoeConfig(n_experts=8, top_k=2, n_shared=1, d_ff=128) if args.moe else None
    cfg = TransformerConfig(
        name="lm-example", n_layers=4, d_model=128, n_heads=8, n_kv_heads=4,
        d_ff=512, vocab=512, moe=moe,
    )
    print(f"params: {cfg.param_count()/1e6:.2f}M "
          f"(active {cfg.active_param_count()/1e6:.2f}M)")

    params = init_params(cfg, jax.random.PRNGKey(0))
    data_cfg = LmDataConfig(vocab=512, seq_len=128, batch=8, seed=0)
    data = map(lambda b: {k: jnp.asarray(v) for k, v in b.items()}, lm_token_stream(data_cfg))

    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer = Trainer(
            lambda p, b: loss_fn(cfg, p, b),
            params,
            AdamWConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps),
            TrainerConfig(
                total_steps=args.steps, ckpt_dir=ckpt_dir, ckpt_every=50, log_every=20,
            ),
            # inject a failure mid-run to demonstrate recovery
            fault_injector=FaultInjector(fail_at_steps=(args.steps // 2,)),
        )
        final = trainer.fit(data)
        print("final metrics:", {k: round(v, 4) for k, v in final.items()})
        losses = [m["loss"] for m in trainer.metrics_log]
        print(f"loss: {losses[0]:.3f} → {losses[-1]:.3f} "
              f"({'decreasing ✓' if losses[-1] < losses[0] else 'NOT decreasing ✗'})")
        print(f"stragglers detected: {trainer.straggler.stragglers_detected}, "
              f"re-dispatches: {trainer.straggler.redispatches}")


if __name__ == "__main__":
    main()
