"""Token batches of a traffic mix, made from the seed on the host.

A copy of the zipf generator of ``repro.data.pipeline.make_batch``: the
benchmark's reference reads its batches from here, so it takes nothing the
program made.  ``fit`` cells let the program's own ``Trainer`` make its
batches, and the comparison with the reference then also checks that the
program trained on these rows.
"""
from __future__ import annotations

import numpy as np


def batch(seed: int, step: int, *, rows: int, seq_len: int,
          vocab: int) -> dict:
    """Batch ``step`` of a closed loop: ``tokens`` and next-token ``labels``,
    both (rows, seq_len) int32, zipf-distributed unigrams."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    u = rng.random((rows, seq_len + 1))
    seq = np.minimum((u ** -1.25 - 1).astype(np.int64), vocab - 1)
    seq = (seq * 2654435761) % vocab
    return {"tokens": seq[:, :-1].astype(np.int32),
            "labels": seq[:, 1:].astype(np.int32)}
