"""A training job's data: seeded token sequences with a labelling the
model can learn, as one host array that the trainer's own DataLoader
batches. Every step sees a batch it has not seen in this epoch."""
from __future__ import annotations

from typing import Dict

import numpy as np


def schedule(seed: int, seconds: float, params: Dict, sizes: Dict) -> Dict:
    """``params``: ``global_batch``, ``seq``, ``max_steps_per_s`` (sizes
    one epoch; past it the loader starts the next epoch over the same
    data), ``labels``: ``"next_id"`` = (token + 1) mod vocabulary."""
    rs = np.random.RandomState(seed)
    vocab = int(sizes["vocab_size"])
    batch, seq = int(params["global_batch"]), int(params["seq"])
    steps = max(8, int(np.ceil(float(params["max_steps_per_s"]) * seconds)))
    tokens = rs.randint(0, vocab, size=(steps * batch, seq)).astype(np.int32)
    if params.get("labels", "next_id") != "next_id":
        raise ValueError(f"unknown labelling {params['labels']!r}")
    labels = ((tokens + 1) % vocab).astype(np.int32)
    return {"mode": "batches", "global_batch": batch, "seq": seq, "tokens": tokens, "labels": labels}
