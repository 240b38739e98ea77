"""End-to-end training with checkpointing + mid-run node failure and
bit-exact resume from the cache replica (paper Fig 7, as training).

    PYTHONPATH=src python examples/train_failover.py
"""
import sys

from repro.launch import train

if __name__ == "__main__":
    train.main(["--arch", "gemma3-1b-reduced", "--steps", "14",
                "--ckpt-every", "4", "--inject-failure", "10"]
               + sys.argv[1:])
