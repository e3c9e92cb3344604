"""The general generators of traffic. A mix is a data file under
`traffic/` that names its generator (`"generator"`); these functions read
its parameters and the seed and know no mix by name. The same seed gives
the same inputs; every seed gives the same sizes, so that no seed is more
work than another.
"""
import numpy as np


def _rng(seed, *salt):
    return np.random.default_rng([int(seed) % (2 ** 63), *salt])


def train_batches(traffic, cfg, seed):
    """Endless batches for a training job, each fresh from the seed:
    {"input_ids", "labels"} and, for a mix with padding, "attention_mask",
    "token_type_ids", "nsp_labels". `labels`: "next_token" (a row of seq+1
    ids, shifted) or "mlm" (`mask_share` of the real positions labelled,
    the rest -100)."""
    b, s, vocab = traffic["batch"], traffic["seq"], cfg["vocab_size"]
    lengths = traffic.get("lengths")
    step = 0
    while True:
        rng = _rng(seed, step)
        step += 1
        if traffic["labels"] == "next_token":
            ids = rng.integers(0, vocab, (b, s + 1), dtype=np.int32)
            yield {"input_ids": ids[:, :-1].copy(), "labels": ids[:, 1:].copy()}
            continue
        lens = rng.permutation(np.asarray(lengths if lengths else [s] * b))
        real = np.arange(s)[None, :] < lens[:, None]
        # id 0 is padding; real tokens never draw it
        ids = np.where(real, rng.integers(1, vocab, (b, s), dtype=np.int32), 0)
        labelled = real & (rng.random((b, s)) < traffic["mask_share"])
        # every row has at least one label, so no row is all ignored
        labelled[np.arange(b), 0] = True
        batch = {"input_ids": ids.astype(np.int32),
                 "labels": np.where(labelled, ids, -100).astype(np.int32),
                 "attention_mask": real.astype(np.int32),
                 "token_type_ids": (real & (np.arange(s)[None, :]
                                            >= lens[:, None] // 2)
                                    ).astype(np.int32)}
        if traffic.get("nsp"):
            batch["nsp_labels"] = rng.integers(0, 2, (b,), dtype=np.int32)
        yield batch


def waves(traffic, cfg, seed, index):
    """Wave number `index`: for each group of the mix, its prompts (lists of
    token ids). The lengths are the mix's fixed set in the file's order, for
    every seed and every wave, so that every wave of every run has the same
    schedule (the order in which prompts of different lengths take their
    slots moves the median time to first token by 1.4%, more than all else
    together); the ids are fresh from the seed in every wave."""
    ids = _rng(seed, 0x1D5, index)
    return [[ids.integers(0, cfg["vocab_size"], int(n)).tolist()
             for n in g["prompt_lengths"]] for g in traffic["groups"]]


def of(traffic):
    """The generator the mix's file names."""
    name = traffic["generator"]
    if name not in ("train_batches", "waves"):
        raise ValueError(f"no traffic generator {name!r}")
    return globals()[name]
