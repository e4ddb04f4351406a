"""The one traffic generator. A traffic mix is a data file of parameters
(`traffic/<name>.json`); this module turns it and `--seed` into requests
or batches. Every seed replays the SAME schedule of sizes with its own token
ids, pixels and weights: the seed must not change how much work a run holds
nor when it comes. (Another order puts other requests at the window's
edges and other lengths side by side in the slots: PR 23 read 783 to 847
tokens/s, then counted by whole requests, on three seeds that differed in
order alone.)

kinds:
  closed_loop  `callers` callers, each sending its next request when the
               last one came back. `prompt` and `output` are length
               distributions; `pool` pairs of lengths are taken from their
               quantiles (not drawn), paired and ordered by fixed
               shuffles; the seed draws the token ids.
  fed_steps    a training loop fed by `pool_batches` host batches of
               `batch` rows made from the seed, cycled in order.
"""
from __future__ import annotations

import json
import math
import os
import statistics


def load(path):
    with open(path) as f:
        spec = json.load(f)
    if spec.get("kind") not in ("closed_loop", "fed_steps"):
        raise ValueError(f"{path}: unknown traffic kind {spec.get('kind')!r}")
    spec["name"] = os.path.splitext(os.path.basename(path))[0]
    return spec


def _quantile_lengths(dist, n):
    """n lengths at the quantiles (i + 0.5) / n of the distribution,
    clipped to [min, max] and rounded down to `multiple_of`."""
    lo, hi = dist["min"], dist["max"]
    step = dist.get("multiple_of", 1)
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist["dist"] == "uniform":
            v = lo + u * (hi - lo)
        elif dist["dist"] == "lognormal":
            v = dist["median"] * math.exp(
                dist["sigma"] * statistics.NormalDist().inv_cdf(u))
        else:
            raise ValueError(f"unknown length distribution {dist['dist']!r}")
        v = int(min(hi, max(lo, round(v))))
        out.append(max(step, v // step * step))
    return out


def length_pool(spec):
    """The fixed set of (prompt, output) lengths of a closed-loop mix."""
    import numpy as np
    n = spec["pool"]
    prompts = _quantile_lengths(spec["prompt"], n)
    outputs = _quantile_lengths(spec["output"], n)
    pairing = np.random.default_rng(0).permutation(n)
    return [(prompts[i], outputs[int(j)]) for i, j in enumerate(pairing)]


def requests(spec, seed, vocab):
    """Endless iterator of (index, prompt ids int32, output length): the
    pool in a fixed shuffled order, again in another fixed order when it
    runs out. Ids are drawn from the seed, in [1, vocab)."""
    import numpy as np
    pool = length_pool(spec)
    index = 0
    epoch = 0
    while True:
        order = np.random.default_rng([7, epoch]).permutation(
            len(pool))
        for j in order:
            p, n = pool[int(j)]
            ids = np.random.default_rng([int(seed), 11, index]).integers(
                1, vocab, size=p, dtype=np.int64).astype(np.int32)
            yield index, ids, n
            index += 1
        epoch += 1


def host_batches(spec, seed, model):
    """The `pool_batches` host batches of a fed_steps mix: float32 images
    uniform in [-1, 1) and int32 labels, every row different."""
    import numpy as np
    hw, c = model["input_hw"], model["in_channels"]
    out = []
    for i in range(spec["pool_batches"]):
        rng = np.random.default_rng([int(seed), 13, i])
        x = rng.random((spec["batch"], hw, hw, c), dtype=np.float32)
        x = x * np.float32(2.0) - np.float32(1.0)
        y = rng.integers(0, model["classes"], size=(spec["batch"],),
                         dtype=np.int64).astype(np.int32)
        out.append((x, y))
    return out


def cycle(batches):
    while True:
        for b in batches:
            yield b
