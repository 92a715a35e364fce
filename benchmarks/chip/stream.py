"""The benchmark's own stream generators (NumPy only, no JAX).

Copied, not imported, from the program so that a later edit there cannot
move the yardstick: ``zipf_probs`` is ``chip_smoke._zipf_probs`` and
``make_dataset`` is ``repro.data.datasets.make_dataset`` (the paper's
section 4.1 shapes).  ``tests/test_selfcheck.py`` holds them equal to the
originals for fixed seeds.

A body is one ``POST /ingest`` of one key.  Every seed draws the same
multiset of key ranks and body sizes (stratified quantiles, shuffled by
the seed), so seeds change the order and the values, not the amount of
work.
"""

from __future__ import annotations

import json
import zlib

import numpy as np

DATASETS = ("pareto", "span", "power")


def zipf_probs(num_keys: int, rng) -> tuple[np.ndarray, np.ndarray]:
    """Zipf(0.99) popularity over the keys (YCSB's default constant); the
    rank -> key order is a seeded permutation so hot keys are scattered."""
    p = 1.0 / np.arange(1, num_keys + 1, dtype=np.float64) ** 0.99
    perm = rng.permutation(num_keys)
    probs = np.empty(num_keys)
    probs[perm] = p / p.sum()
    return probs, perm


def make_dataset(name: str, n: int, seed: int = 0) -> np.ndarray:
    """``pareto`` (Pareto(1, 1)), ``span`` (trace span durations, 100 ns to
    1.9e12 ns) and ``power`` (household power, 0.076 to 11.122 kW)."""
    rng = np.random.default_rng((seed, zlib.crc32(name.encode()) & 0xFFFF))
    if name == "pareto":
        return rng.pareto(1.0, n) + 1.0
    if name == "span":
        body = rng.lognormal(mean=11.5, sigma=1.8, size=n)
        tail_mask = rng.random(n) < 0.02
        tail = (rng.pareto(0.9, n) + 1.0) * 1e8
        out = np.where(tail_mask, tail, body)
        return np.clip(np.round(out), 100, 1.9e12)
    if name == "power":
        comp = rng.random(n) < 0.7
        low = rng.lognormal(mean=np.log(0.35), sigma=0.45, size=n)
        high = rng.lognormal(mean=np.log(2.2), sigma=0.55, size=n)
        return np.clip(np.where(comp, low, high), 0.076, 11.122)
    raise KeyError(f"unknown dataset {name!r}; options: {DATASETS}")


def key_name(i: int) -> str:
    return f"/k{int(i):05d}"


def families(num_keys: int, fam: dict) -> tuple[np.ndarray, np.ndarray]:
    """(wide, neg) masks: wide keys carry ``span`` values, neg keys negated
    ``power`` values, the rest ``pareto`` values (``chip_smoke.py``'s mix)."""
    idx = np.arange(num_keys)
    wide = idx % fam["wide_every"] == fam["wide_phase"]
    neg = (idx % fam["neg_every"] == fam["neg_phase"]) & ~wide
    return wide, neg


def stratified(n: int, rng) -> np.ndarray:
    """``n`` evenly spaced probabilities in (0, 1), shuffled by ``rng``."""
    u = (np.arange(n) + 0.5) / n
    rng.shuffle(u)
    return u


def body_sizes(n: int, spec: dict, rng) -> np.ndarray:
    """Values per POST: log-uniform on [low, high], stratified."""
    lo, hi = np.log(spec["low"]), np.log(spec["high"])
    return np.round(np.exp(lo + (hi - lo) * stratified(n, rng))).astype(np.int64)


def key_probs(num_keys: int, seed: int) -> np.ndarray:
    """The run's key popularity: one seeded Zipf permutation shared by every
    stream and process of the run."""
    return zipf_probs(num_keys, np.random.default_rng(sub_seed(seed, 1)))[0]


def body_keys(n: int, probs: np.ndarray, rng) -> np.ndarray:
    """Key of each POST, Zipf(0.99) by inverse CDF of stratified draws."""
    cdf = np.cumsum(probs)
    cdf[-1] = 1.0
    return np.searchsorted(cdf, stratified(n, rng), side="right").astype(np.int64)


def draw_values(keys: np.ndarray, sizes: np.ndarray, num_keys: int, fam: dict,
                seed: int) -> list[np.ndarray]:
    """float64 values of each body from its key's family.

    Non-wide values are capped at ``fam["value_cap"]``: a value above the
    bank's level-0 range would clamp, and the guarantee is stated for values
    inside it (Pareto(1) passes 1e8 with probability 1e-8 per value).
    """
    wide, neg = families(num_keys, fam)
    total = int(sizes.sum())
    pareto = np.minimum(make_dataset("pareto", total, seed), fam["value_cap"])
    power = make_dataset("power", total, seed)
    span = make_dataset("span", total, seed)
    lane_key = np.repeat(keys, sizes)
    vals = np.where(neg[lane_key], -power, pareto)
    vals = np.where(wide[lane_key], span, vals)
    return np.split(vals, np.cumsum(sizes)[:-1])


def format_body(key: str, values: np.ndarray) -> tuple[bytes, np.ndarray]:
    """The POST body text and the float32 values the server parses from it."""
    text = ",".join(["%.7g" % v for v in values.tolist()])
    body = ('{"key": "%s", "values": [%s]}' % (key, text)).encode()
    parsed = np.asarray(json.loads("[" + text + "]"), np.float32)
    return body, parsed


def make_pool(n: int, probs: np.ndarray, sizes_spec: dict, fam: dict, seed: int,
              *, text: bool = True):
    """``n`` bodies: (key ids, float32 value arrays, POST bodies or None)."""
    num_keys = probs.size
    rng = np.random.default_rng(seed)
    keys = body_keys(n, probs, rng)
    sizes = body_sizes(n, sizes_spec, rng)
    vals = draw_values(keys, sizes, num_keys, fam, seed)
    if not text:
        return keys, [v.astype(np.float32) for v in vals], None
    bodies, parsed = [], []
    for k, v in zip(keys.tolist(), vals):
        b, p = format_body(key_name(k), v)
        bodies.append(b)
        parsed.append(p)
    return keys, parsed, bodies


def sub_seed(seed: int, *parts: int) -> int:
    """A 63-bit seed derived from the run's seed and a stream position."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), *[int(p) for p in parts]])
    return int(ss.generate_state(1, np.uint64)[0] >> np.uint64(1))


def arrivals(rate: float, seconds: float, rng) -> np.ndarray:
    """Open-loop due offsets in [0, seconds): ``rate * seconds`` arrivals whose
    gaps are the stratified quantiles of an exponential (Poisson arrivals
    with the same set of gaps for every seed, in the seed's order)."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-stratified(n, rng))
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (seconds / gaps.sum())
