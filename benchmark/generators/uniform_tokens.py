"""Token ids drawn uniformly over the vocabulary: ``rows_per_chip`` rows of
``seq_len`` ids a chip a step, every row different, a fresh batch every step
from (seed, step), the same for the same seed.

A generator is named by a traffic file's ``generator`` and gives
``batch(conf, traffic, seed, step, chips)``: what one step feeds, name ->
array with rows leading, on the host; and ``tokens_per_step(traffic, chips)``:
what that step counts for in ``tokens_per_s_per_chip``."""

import numpy as np


def batch(conf, traffic, seed, step, chips):
    """{"tokens": [rows_per_chip * chips, seq_len] int32} of step ``step``
    (from 1)."""
    rng = np.random.default_rng([int(seed), int(step)])
    rows = traffic["rows_per_chip"] * chips
    return {"tokens": rng.integers(
        0, conf["vocab_size"], (rows, traffic["seq_len"]), dtype=np.int32)}


def tokens_per_step(traffic, chips):
    return traffic["rows_per_chip"] * chips * traffic["seq_len"]
