"""One general traffic generator, driven by a mix file and the cell's rate.

Every seed gets the same work: the multiset of prompt lengths, of output
lengths and of inter-arrival gaps is fixed by the mix (stratified quantiles
of its distributions), and the seed only permutes them and draws the token
ids. Runs on different seeds then differ by order, as runs of one seed
differ by timing, and not by how much there was to do.

Mix file keys:

  arrivals   {"kind": "backlog", "requests": N}: all N due at t = 0 (an
             offline queue);
             {"kind": "poisson", "tail_s": T}: open-loop arrivals at the
             cell's ``rate_per_s`` until ``seconds + T``.
  prompt     {"kind": "choice", "values": [...]}: uniform over the values;
             {"kind": "lognormal_ladder", "median": m, "sigma": s,
             "values": [...]}: a lognormal length rounded up to the next
             rung of the ladder (the top rung caps it).
  output     {"kind": "uniform", "low": a, "high": b}: whole numbers a..b;
             {"kind": "lognormal", "median": m, "sigma": s, "low": a,
             "high": b}: a rounded lognormal clipped to a..b.
  slots, max_seq_len   the engine's shape for this mix.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


@dataclasses.dataclass(frozen=True)
class Item:
    """One request as the benchmark made it."""

    id: int
    prompt: tuple
    max_new_tokens: int
    due: float


def _kind(spec: dict, *known: str) -> str:
    if spec.get("kind") not in known:
        raise ValueError(f"mix entry {spec!r}: kind must be one of {known}")
    return spec["kind"]


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(u, median, sigma):
    return np.array([median * math.exp(sigma * _NORMAL.inv_cdf(float(p)))
                     for p in u])


def prompt_lengths(spec: dict, n: int) -> np.ndarray:
    u = _quantiles(n)
    vals = np.asarray(sorted(spec["values"]), np.int64)
    if _kind(spec, "choice", "lognormal_ladder") == "choice":
        return vals[np.minimum((u * len(vals)).astype(np.int64),
                               len(vals) - 1)]
    x = _lognormal(u, spec["median"], spec["sigma"])
    idx = np.searchsorted(vals, x, side="left")
    return vals[np.minimum(idx, len(vals) - 1)]


def output_lengths(spec: dict, n: int) -> np.ndarray:
    u = _quantiles(n)
    lo, hi = int(spec["low"]), int(spec["high"])
    if _kind(spec, "uniform", "lognormal") == "uniform":
        return lo + np.minimum((u * (hi - lo + 1)).astype(np.int64), hi - lo)
    x = np.rint(_lognormal(u, spec["median"], spec["sigma"]))
    return np.clip(x, lo, hi).astype(np.int64)


def request_count(mix: dict, cell: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if _kind(arr, "backlog", "poisson") == "backlog":
        return int(arr["requests"])
    return int(math.ceil(cell["rate_per_s"] * (seconds + arr["tail_s"])))


def due_times(mix: dict, cell: dict, n: int, rng) -> np.ndarray:
    arr = mix["arrivals"]
    if arr["kind"] == "backlog":
        return np.zeros(n)
    # exponential gaps at stratified quantiles, in the seed's order
    gaps = -np.log1p(-_quantiles(n)) / cell["rate_per_s"]
    return np.cumsum(rng.permutation(gaps))


def generate(mix: dict, cell: dict, *, seed: int, seconds: float,
             vocab: int, id0: int = 0) -> list[Item]:
    """The requests of one run: same sizes for every seed, in the seed's
    order, with the seed's token ids."""
    n = request_count(mix, cell, seconds)
    rng = np.random.default_rng(int(seed))
    plen = rng.permutation(prompt_lengths(mix["prompt"], n))
    olen = rng.permutation(output_lengths(mix["output"], n))
    due = due_times(mix, cell, n, rng)
    return [Item(id=id0 + i,
                 prompt=tuple(int(t) for t in rng.integers(0, vocab,
                                                           int(plen[i]))),
                 max_new_tokens=int(olen[i]), due=float(due[i]))
            for i in range(n)]


# ------------------------------------------------- shapes the mix can reach


def length_bounds(mix: dict) -> dict:
    """Smallest and largest prompt and output length the mix can draw."""
    p, o = mix["prompt"], mix["output"]
    return {"prompts": sorted(int(v) for v in p["values"]),
            "out_low": int(o["low"]), "out_high": int(o["high"])}


def decode_widths(mix: dict, block_size: int) -> range:
    """Decode table widths (pages) a step can reach: a step's width is
    ceil((longest live length + 1) / block_size); a sequence is live from
    its prompt length up to prompt + output - 2 tokens in cache."""
    b = length_bounds(mix)
    lo = -(-(b["prompts"][0] + 1) // block_size)
    hi = -(-(b["prompts"][-1] + b["out_high"] - 1) // block_size)
    return range(lo, hi + 1)


def page_counts(mix: dict, block_size: int) -> list[int]:
    """Pages a request holds from admission to finish, over the mix."""
    b = length_bounds(mix)
    return sorted({-(-(p + o) // block_size) for p in b["prompts"]
                   for o in range(b["out_low"], b["out_high"] + 1)})


def warmup_items(mix: dict, *, vocab: int, seed: int, id0: int) -> list[Item]:
    """One short request per prompt rung: every prefill program the mix
    can reach, a decode step, a finish."""
    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    return [Item(id=id0 + i,
                 prompt=tuple(int(t) for t in rng.integers(0, vocab, p)),
                 max_new_tokens=2, due=0.0)
            for i, p in enumerate(length_bounds(mix)["prompts"])]
