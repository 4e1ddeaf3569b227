"""Traffic of a block-diffusion training cell: beside the token ids, the
noise. A mix is a data file of parameters; what a seed changes is the ids,
the rates and which tokens are masked, never how much work is offered.

``ring`` host batches, each ``Batch(ids, rates, masked)``: ``batch`` rows of
``seq`` clean token ids below the mask id (no shift: position i predicts
token i), one rate t ~ U(``rate_low``, 1) for each block of ``block_length``
tokens, and each token masked independently with its block's rate. Ids,
rates and masks come from streams of their own (``traffic.rng_for``), so
every batch of the ring differs in all three.
"""
from typing import NamedTuple

import numpy as np

from .traffic import rng_for

IDS, RATES, MASKS = 11, 12, 13  # streams; traffic.train_batches has 1


class Batch(NamedTuple):
    ids: np.ndarray     # [batch, seq] int32, below the mask id
    rates: np.ndarray   # [batch, seq / block_length] float32
    masked: np.ndarray  # [batch, seq] bool


def train_batches(params, seed, mask_id):
    """``ring`` batches; clean ids are drawn below ``mask_id``."""
    ring, batch, seq = params["ring"], params["batch"], params["seq"]
    block = params["block_length"]
    if seq % block:
        raise ValueError(f"seq {seq} is not whole blocks of {block}")
    ids = rng_for(seed, IDS).integers(0, mask_id, (ring, batch, seq),
                                      dtype=np.int32)
    rates = rng_for(seed, RATES).uniform(
        params["rate_low"], 1.0, (ring, batch, seq // block)).astype(
            np.float32)
    draws = rng_for(seed, MASKS).random((ring, batch, seq), dtype=np.float32)
    masked = draws < np.repeat(rates, block, axis=-1)
    return [Batch(*parts) for parts in zip(ids, rates, masked)]


def weights(batch, block):
    """[batch, seq] float32: 1 / t of its block where a token is masked, 0
    elsewhere."""
    return np.where(batch.masked, 1.0 / np.repeat(batch.rates, block, -1),
                    0.0).astype(np.float32)


def first_tokens(batch, tokens, block):
    """The batch cut to each row's first ``tokens`` tokens (whole blocks)."""
    return Batch(batch.ids[:, :tokens], batch.rates[:, :tokens // block],
                 batch.masked[:, :tokens])
