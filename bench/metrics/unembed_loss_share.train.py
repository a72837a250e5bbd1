"""Share of the training step's device time in the vocabulary: operations
under the ``unembed`` and ``loss`` scopes (the logits over the whole
vocabulary, the log-softmax and the cross-entropy), forward and backward,
over all operations of the step program in the trace.

Layer: train step (``models/transformer.unembed_logits`` and ``lm_loss``
under ``train/train_step.py``).  Moves ``train_tokens_per_s``.
"""
from lib import scopes, trace

LAYER = "train step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"
PROGRAM = r"train_step"


def in_vocabulary(segments):
    return "unembed" in segments or "loss" in segments


def read(reading):
    sc = scopes.scoped(reading)
    if sc is None:
        return None
    summary = sc["summary"]
    part, _ = scopes.scope_seconds(summary, PROGRAM, in_vocabulary)
    dev_s = trace.seconds_in(summary, "programs_s", PROGRAM)
    if part <= 0 or dev_s <= 0:
        return None
    return 100.0 * part / dev_s
