"""End-to-end driver: train a ~100M-parameter decoder for a few hundred steps
with guided synchronous SGD on the synthetic Markov LM stream.

This wraps the production launcher (repro.launch.train) with a 100M config
derived from minicpm-2b (same family, fewer layers). On a TPU mesh pass
--mesh prod; on this CPU host expect a few seconds per step at the default
sizes — use --steps/--d-model to trade fidelity for time.

Run:  PYTHONPATH=src python examples/train_100m.py [--steps 300]
"""
import argparse
import sys

from repro.launch.train import main as train_main

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--seq", type=int, default=128)
ap.add_argument("--batch", type=int, default=8)
ap.add_argument("--d-model", type=int, default=576)
ap.add_argument("--layers", type=int, default=12)
ap.add_argument("--mesh", default="local")
args = ap.parse_args()

# minicpm-2b family at ~135M: 12 layers x d_model 576, d_ff 2304 + tied 122k-vocab embed.
# It keeps MiniCPM-2B's published multipliers at this width and depth (embeddings
# x 12, residual branches x 1.4/sqrt(40), logits x 256/2304), not the muP values
# a 576-wide, 12-layer MiniCPM would take (logits x 256/576).
argv = [
    "--arch", "minicpm-2b",
    "--layers", str(args.layers), "--d-model", str(args.d_model), "--d-ff", "2304",
    "--steps", str(args.steps), "--seq", str(args.seq), "--batch", str(args.batch),
    "--mode", "ssgd", "--strategy", "guided_fused", "--rho", "10", "--workers", "4",
    "--optimizer", "sgd", "--lr", "0.05", "--schedule", "wsd",
    "--mesh", args.mesh, "--log-every", "10",
    "--ckpt-dir", "results/ckpt_100m", "--ckpt-every", "100",
    "--metrics-out", "results/train_100m.json",
]
history = train_main(argv)
first, last = history[0]["loss"], history[-1]["loss"]
print(f"\ntrained: loss {first:.3f} -> {last:.3f} "
      f"({'DECREASED' if last < first else 'check hyperparams'})")
