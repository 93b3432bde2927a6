"""The general traffic generators: one for each ``generator`` a traffic
file may name. A mix is DATA (``benchmark/traffic/<mix>.json``); these
functions are the only code that reads it, so a later PR adds a mix by
adding a file.

Sizes are drawn CONTINUOUSLY from the distributions the file states, anew
for every seed. So that runs with different seeds still do the same work,
the draws are stratified: a pass of ``strata`` requests takes one value
from each of ``strata`` equal-probability slices of each distribution, at a
seed-drawn point inside the slice, the two axes paired in a seed-drawn
order (a Latin hypercube). Every pass covers both distributions evenly,
whatever the seed; no size is fixed, and none is a round number.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    # seeds may exceed 2**31: SeedSequence takes any non-negative int
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFFFFFF, int(stream)]))


def _inverse_cdf(spec: Dict[str, Any], q: np.ndarray) -> np.ndarray:
    """Whole numbers in ``[low, high]`` at the quantiles ``q`` of the
    distribution the spec names."""
    lo, hi = float(spec["low"]), float(spec["high"])
    if spec["dist"] == "uniform":
        v = lo + q * (hi - lo)
    elif spec["dist"] == "log_uniform":
        v = np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(int)


def lm_sizes(traffic: Dict[str, Any], rng: np.random.Generator
             ) -> Iterator[tuple]:
    """An endless stream of (prompt tokens, max new tokens): pass after
    pass of ``strata`` jittered, stratified draws of each axis."""
    n = int(traffic["strata"])
    while True:
        qp = (rng.permutation(n) + rng.random(n)) / n
        qm = (rng.permutation(n) + rng.random(n)) / n
        yield from zip(_inverse_cdf(traffic["prompt_tokens"], qp),
                       _inverse_cdf(traffic["max_new_tokens"], qm))


def closed_loop_lm(traffic: Dict[str, Any], vocab_size: int, seed: int,
                   stream: int) -> Iterator[Dict[str, Any]]:
    """An endless stream of requests with token ids uniform over the
    vocabulary. ``stream`` separates warm-up (1) from the measured window
    (0)."""
    rng = _rng(seed, stream)
    for n, (p, m) in enumerate(lm_sizes(traffic, rng)):
        yield {"id": f"s{stream}-{n}",
               "prompt": rng.integers(0, vocab_size, size=int(p),
                                      dtype=np.int64).astype(np.int32),
               "max_new": int(m)}


def image_classification(traffic: Dict[str, Any], seed: int
                         ) -> Dict[str, np.ndarray]:
    """``n_examples`` uint8 images of class templates plus noise, with
    their labels — the arithmetic of the program's own synthetic generator
    (``rafiki_tpu.data.generate_image_classification_dataset``), copied
    here so the yardstick cannot move; in float32, in place, and in blocks
    of 128 images over four threads, because every run of every check pays
    this as set-up. Each block draws its noise from a stream of its own, so
    the result does not depend on the threads' timing."""
    from concurrent.futures import ThreadPoolExecutor

    n, hw = int(traffic["n_examples"]), int(traffic["image_size"])
    c, k = int(traffic["n_channels"]), int(traffic["n_classes"])
    noise = float(traffic.get("noise", 0.25))
    coarse = _rng(7 + k * 1000 + hw, 1).normal(
        0.0, 1.0, size=(k, 7, 7, c)).astype(np.float32)
    up = np.minimum(np.arange(hw) // int(np.ceil(hw / 7)), 6)
    labels = _rng(seed, 0).integers(0, k, size=n).astype(np.int64)
    bound = 3.0 + 3.0 * noise * 2.0
    images = np.empty((n, hw, hw, c), np.uint8)
    block = 128

    def fill(lo: int) -> None:
        lab = labels[lo:lo + block]
        x = _rng(seed, 1000 + lo).standard_normal(
            (len(lab), hw, hw, c), dtype=np.float32)
        x *= np.float32(noise * 2.0)
        x += coarse[lab][:, up][:, :, up]  # 7x7 grids, upsampled
        x += np.float32(bound)
        x *= np.float32(255.0 / (2.0 * bound))
        np.clip(x, 0.0, 255.0, out=x)
        images[lo:lo + block] = x.astype(np.uint8)

    with ThreadPoolExecutor(max_workers=4) as pool:
        list(pool.map(fill, range(0, n, block)))
    return {"images": images, "labels": labels, "n_classes": np.asarray(k)}
