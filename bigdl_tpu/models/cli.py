"""Shared train-CLI plumbing for the model mains.

Reference analogue: the common scopt options each ``DL/models/*/Utils.scala``
re-declares (dataFolder, batchSize, maxEpoch, learningRate, checkpoint) —
centralized here so the five mains share one parser tail and one
optimizer-wiring tail.
"""

from __future__ import annotations

import argparse
import logging
from typing import Optional


def make_parser(name: str, batch_size: int, max_epoch: int,
                learning_rate: float, folder_help: str) -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(name)
    parser.add_argument("-f", "--folder", default=None, help=folder_help)
    parser.add_argument("-b", "--batchSize", type=int, default=batch_size)
    parser.add_argument("-e", "--maxEpoch", type=int, default=max_epoch)
    parser.add_argument("--maxIteration", type=int, default=0,
                        help="overrides maxEpoch when > 0")
    parser.add_argument("--learningRate", type=float, default=learning_rate)
    parser.add_argument("--checkpoint", default=None)
    return parser


def fit(opt, args, checkpoint_trigger=None):
    """Wire the shared end/checkpoint policy and run (the tail every
    Train.scala repeats)."""
    from bigdl_tpu.core.engine import enable_compile_cache
    from bigdl_tpu.optim import Trigger

    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()
    opt.set_end_when(Trigger.max_iteration(args.maxIteration)
                     if args.maxIteration else Trigger.max_epoch(args.maxEpoch))
    if args.checkpoint:
        opt.set_checkpoint(args.checkpoint,
                           checkpoint_trigger or Trigger.every_epoch())
    return opt.optimize()
