"""Serve a (post-training-assembled) model with batched requests: one
prefill + greedy decode loop with a KV cache — the inference side of the
framework.

    PYTHONPATH=src python examples/serve_batched.py --arch minitron-4b
    PYTHONPATH=src python examples/serve_batched.py --arch falcon-mamba-7b
      (attention-free: O(1) state instead of a KV cache)
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch import serve as serve_cli

parser = argparse.ArgumentParser()
parser.add_argument("--arch", default="minitron-4b")
args = parser.parse_args()

serve_cli.main(["--arch", args.arch, "--batch", "4", "--prompt-len", "32",
                "--decode-steps", "16"])
