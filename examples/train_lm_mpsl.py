"""End-to-end driver: MPSL-fine-tune an assigned LM architecture with the
fault-tolerant trainer (checkpointing, straggler masking), then resume
after a simulated failure.

    PYTHONPATH=src python examples/train_lm_mpsl.py [--arch minitron-4b]
    PYTHONPATH=src python examples/train_lm_mpsl.py --arch qwen2-moe-a2.7b

Reduced same-family configs run on CPU; the full-size run is
``python -m repro.launch.train --full`` over every device the process
sees.
"""
import argparse
import os
import shutil
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch import train as train_cli

parser = argparse.ArgumentParser()
parser.add_argument("--arch", default="minitron-4b")
parser.add_argument("--steps", type=int, default=40)
args = parser.parse_args()

ckpt = "/tmp/mpsl_example_ckpt"
shutil.rmtree(ckpt, ignore_errors=True)

print(f"=== phase 1: train {args.arch} for {args.steps//2} steps, with "
      f"10% simulated client dropout ===")
train_cli.main(["--arch", args.arch, "--steps", str(args.steps // 2),
                "--ckpt-dir", ckpt, "--ckpt-every", "10",
                "--drop-prob", "0.1"])

print("=== simulated failure: process 'dies'; restarting from latest "
      "checkpoint ===")
train_cli.main(["--arch", args.arch, "--steps", str(args.steps),
                "--ckpt-dir", ckpt, "--ckpt-every", "10",
                "--drop-prob", "0.1"])
print("=== resumed run completed — loss continued from the checkpoint ===")
