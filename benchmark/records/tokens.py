"""Token sequences of one length, uniform over the vocabulary."""

import numpy as np

ROW_KEY = "tokens"


def make(spec, model, rng):
    return {"tokens": rng.integers(0, model["vocab_size"], spec["seq_len"],
                                   dtype=np.int32)}
