"""The whole training step's share of the chips' peak, by the host's clock:
tokens per second of the traced run's window times the operations one
position needs (``counts/transformer.train_ops_per_sequence`` over the
sequence length; forward and backward, no recomputation) over chips times
the bf16 peak.

Layer: train step, whole step (``launch/steps.build_cell``,
``train/train_step.py``).  Moves ``train_tokens_per_s``.
"""
import os

from lib import common

LAYER = "train step"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"

counts = common.load_module(os.path.join(common.BENCH, "counts",
                                         "transformer.py"))


def read(reading):
    tps = reading.out["metrics"].get("train_tokens_per_s")
    if not tps:
        return None
    config, seq = reading.ctx.files["config"], reading.ctx.files["mix"]["seq"]
    per_position = counts.train_ops_per_sequence(config, seq) / seq
    chips = len(reading.ctx.devices)
    return 100.0 * tps * per_position / (
        chips * reading.peaks["bf16_flops_per_s"])
