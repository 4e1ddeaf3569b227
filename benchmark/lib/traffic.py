"""The one generator of traffic. A mix is a data file of parameters; what a
seed changes is order and token ids, never how much work is offered.

Training: ``ring`` host batches of ``batch`` rows of ``seq + 1`` token ids,
every row different.
"""
import numpy as np


def rng_for(seed, stream):
    """Independent generator per (seed, stream); any whole-number seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def train_batches(params, seed, vocab):
    """``ring`` arrays [batch, seq + 1] int32 with ids below ``vocab``."""
    rng = rng_for(seed, 1)
    shape = (params["ring"], params["batch"], params["seq"] + 1)
    return list(rng.integers(0, vocab, shape, dtype=np.int32))
