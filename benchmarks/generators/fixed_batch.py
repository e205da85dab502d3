"""One seeded batch held fixed for the whole run (``constant_feeds``): the
training traffic of the first cells.  Parameters come from the traffic
file: ``task`` (``mlm_nsp``), ``batch``, ``seq_len`` and ``max_predictions``.
Every row differs."""
import numpy as np


def generate(traffic, cfg, seed):
    rng = np.random.default_rng(int(seed))
    batch, seq = traffic["batch"], traffic["seq_len"]
    vocab = cfg["vocab_size"]
    ids = rng.integers(1, vocab, size=(batch, seq)).astype(np.int32)
    if traffic["task"] != "mlm_nsp":
        raise ValueError(f"unknown task {traffic['task']!r}")
    n_pred = traffic["max_predictions"]
    positions = np.stack([np.sort(rng.choice(seq, n_pred, replace=False))
                          for _ in range(batch)]).astype(np.int32)
    return {
        "input_ids": ids,
        "token_type_ids": (rng.random((batch, seq)) < 0.5).astype(np.int32),
        "attention_mask": np.ones((batch, seq), np.int32),
        "masked_positions": positions,
        "mlm_labels": np.take_along_axis(ids, positions, axis=1),
        "nsp_labels": rng.integers(0, 2, size=(batch, 1)).astype(np.int32),
    }


def summary(feeds):
    return {k: [list(v.shape), str(v.dtype)] for k, v in feeds.items()}
